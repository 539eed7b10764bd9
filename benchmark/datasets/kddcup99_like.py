"""kddcup99_like — seeded data at the shape of the KDD Cup 1999 Data
(`kddcup.data`, UCI KDD Archive; no network on the chip machine): 41
features of a network connection in the source's order and types — 38
numeric, `protocol_type` (3 levels), `service` (70), `flag` (11) — and a
label of 23 values, `normal` and 22 attack types, at the source's class
counts (`COUNTS`, as recalled: the configuration's file lists them under
`assumed`): three classes hold 99 % of the rows, eight fewer than 13 rows
each. A configuration names this module under `data`, as higgs_like.

Rows are drawn i.i.d. by those frequencies (the source file is in time
order, in bursts), and a row's features from ITS CLASS's tables — which
protocols, services and flags the class prefers, how heavy its byte counts
are, which of its counters and rates are ever off zero — at the source's
ranges: byte counts heavy-tailed up to ~1e9, `count` / `srv_count` 0..511,
the `dst_host_*count` pair 0..255, the fifteen `*_rate` columns in [0, 1]
at two decimals, seven 0/1 flags (`num_outbound_cmds` is 0 in every row of
the source, and here). So several numeric columns and the SET splits on
`service` carry signal, as in the source. The categorical columns hold
LEVEL IDS (f32 like the rest: byte counts do not fit int16), which is what
the frames are built from and what the reference walks.

Rows are made in fixed blocks, each from (seed, block), so the first n
rows are the same whatever the total; the classes' tables come from the
seed alone.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

LABEL = "label"
# the 23 values of the label and their rows in kddcup.data (4,898,431)
COUNTS = {
    "back": 2203, "buffer_overflow": 30, "ftp_write": 8, "guess_passwd": 53,
    "imap": 12, "ipsweep": 12481, "land": 21, "loadmodule": 9, "multihop": 7,
    "neptune": 1072017, "nmap": 2316, "normal": 972781, "perl": 3, "phf": 4,
    "pod": 264, "portsweep": 10413, "rootkit": 10, "satan": 15892,
    "smurf": 2807886, "spy": 2, "teardrop": 979, "warezclient": 1020,
    "warezmaster": 20}
DOMAIN = sorted(COUNTS)
NAMES = [
    "duration", "protocol_type", "service", "flag", "src_bytes", "dst_bytes",
    "land", "wrong_fragment", "urgent", "hot", "num_failed_logins",
    "logged_in", "num_compromised", "root_shell", "su_attempted", "num_root",
    "num_file_creations", "num_shells", "num_access_files",
    "num_outbound_cmds", "is_host_login", "is_guest_login", "count",
    "srv_count", "serror_rate", "srv_serror_rate", "rerror_rate",
    "srv_rerror_rate", "same_srv_rate", "diff_srv_rate",
    "srv_diff_host_rate", "dst_host_count", "dst_host_srv_count",
    "dst_host_same_srv_rate", "dst_host_diff_srv_rate",
    "dst_host_same_src_port_rate", "dst_host_srv_diff_host_rate",
    "dst_host_serror_rate", "dst_host_srv_serror_rate",
    "dst_host_rerror_rate", "dst_host_srv_rerror_rate"]
# levels of each input, 0: numeric
LEVELS = [0, 3, 70, 11] + [0] * 37
# the numeric columns by kind, with the largest value the source holds
HEAVY = {"duration": 58329.0, "src_bytes": 1.4e9, "dst_bytes": 1.4e9}
RARE = {"wrong_fragment": 3, "urgent": 14, "hot": 101,
        "num_failed_logins": 5, "num_compromised": 7479, "num_root": 7468,
        "num_file_creations": 43, "num_shells": 2, "num_access_files": 9}
FLAGS = ["land", "logged_in", "root_shell", "su_attempted",
         "is_host_login", "is_guest_login"]       # + num_outbound_cmds: 0
COUNTERS = {"count": 511, "srv_count": 511, "dst_host_count": 255,
            "dst_host_srv_count": 255}
RATES = [n for n in NAMES if n.endswith("_rate")]
BLOCK = 250_000


def feature_names(cols: int):
    return NAMES[:cols]


def domain_of(j: int):
    """The level names of input j: the three protocols, codes for the
    services and the connection flags."""
    if j == 1:
        return ["icmp", "tcp", "udp"]
    return [f"{'svc' if j == 2 else 'flg'}{i:02d}" for i in range(LEVELS[j])]


def tables(seed: int) -> dict:
    """What the seed fixes for every row: the classes' cumulative
    frequency, and per class (a row of each table) what its connections
    look like."""
    rng = np.random.default_rng([seed, 0xDDC])
    K = len(DOMAIN)
    w = np.array([COUNTS[c] for c in DOMAIN], np.float64)
    t = {"class_cdf": np.cumsum(w / w.sum())}
    for j, k in enumerate(LEVELS):
        if not k:
            continue
        # a class prefers a few levels of its own (a rank order of its own
        # over a Zipf-like profile) over a profile all classes share
        zipf = 1.0 / (1.0 + np.arange(k)) ** 1.1
        shared = zipf[rng.permutation(k)]
        own = np.stack([zipf[rng.permutation(k)] ** 2.5 for _ in range(K)])
        p = 0.8 * own / own.sum(1, keepdims=True) + 0.2 * shared / shared.sum()
        # one ascending table for every class: class c's cdf sits in
        # [c, c + 1), so that u + c finds its level in ONE searchsorted
        t["cdf", j] = (np.cumsum(p, axis=1).clip(max=1.0)
                       + np.arange(K)[:, None]).ravel()
    for name in HEAVY:            # P(off zero), and a log-normal's mu, sigma
        t[name] = np.stack([rng.random(K) ** 2, rng.uniform(0.0, 13.0, K),
                            rng.uniform(0.1, 2.2, K)], 1).astype(np.float32)
    for name in RARE:             # P(off zero), scale of the exponential
        t[name] = np.stack([rng.random(K) ** 4,
                            rng.uniform(0.3, 6.0, K)], 1).astype(np.float32)
    for name in FLAGS:
        t[name] = (rng.random(K) ** 3).astype(np.float32)
    for name in COUNTERS:         # value = top * u ^ power
        t[name] = np.exp(rng.uniform(-2.0, 2.0, K)).astype(np.float32)
    for name in RATES:            # P(0.00), P(1.00); between them uniform
        lo = rng.random(K) * 0.9
        t[name] = np.stack([lo, (1.0 - lo) * rng.random(K)],
                           1).astype(np.float32)
    return t


def host_arrays(rows: int, cols: int, seed: int):
    """(X (rows, cols) f32, y (rows,) int8 class codes into DOMAIN) from
    the seed."""
    assert cols == len(NAMES), "the KDD Cup 1999 table has 41 features"
    X = np.empty((rows, cols), np.float32)
    y = np.empty(rows, np.int8)
    t = tables(seed)
    at = {n: j for j, n in enumerate(NAMES)}

    def fill(b):
        # a block is always drawn whole (a table's last block may be cut):
        # the first n rows are then the same whatever the total
        lo, hi = b * BLOCK, min((b + 1) * BLOCK, rows)
        m = BLOCK
        rng = np.random.default_rng([seed, b])

        def u():
            return rng.random(m, dtype=np.float32)
        c = np.minimum(np.searchsorted(t["class_cdf"], rng.random(m)),
                       len(DOMAIN) - 1)
        y[lo:hi] = c[:hi - lo]
        x = X[lo:hi] if hi - lo == m else np.empty((m, cols), np.float32)
        for j, k in enumerate(LEVELS):
            if k:
                x[:, j] = np.clip(np.searchsorted(
                    t["cdf", j], u().astype(np.float64) + c) - c * k, 0, k - 1)
        for name, top in HEAVY.items():
            p, mu, sigma = t[name][c].T
            v = np.exp(mu + sigma * rng.standard_normal(m, dtype=np.float32))
            x[:, at[name]] = np.where(u() < p, np.minimum(np.floor(v), top), 0)
        for name, top in RARE.items():
            p, scale = t[name][c].T
            v = 1.0 + np.floor(-np.log1p(-u()) * scale)
            x[:, at[name]] = np.where(u() < p, np.minimum(v, top), 0)
        for name in FLAGS:
            x[:, at[name]] = u() < t[name][c]
        x[:, at["num_outbound_cmds"]] = 0
        for name, top in COUNTERS.items():
            x[:, at[name]] = np.floor((top + 1) * u() ** t[name][c]) \
                .clip(max=top)
        for name in RATES:
            p0, p1 = t[name][c].T
            r = u()
            x[:, at[name]] = np.where(
                r < p0, 0.0, np.where(r >= 1.0 - p1, 1.0, np.round(
                    (r - p0) / np.maximum(1.0 - p0 - p1, 1e-6), 2)))
        if hi - lo < m:
            X[lo:hi] = x[:hi - lo]

    with ThreadPoolExecutor(8) as pool:
        list(pool.map(fill, range(-(-rows // BLOCK))))
    return X, y


def frame(X, y):
    """A Frame in the DKV through the public constructors: one Vec per
    host column, a categorical one from its level ids and its domain, the
    label a 23-level categorical (every level in the domain, whether or not
    the rows hold it)."""
    import jax
    from h2o3_tpu.core.frame import Frame, T_CAT, Vec

    def vec(j):
        if j == X.shape[1]:
            return Vec.from_numpy(np.asarray(y, np.float64), type=T_CAT,
                                  domain=DOMAIN)
        if LEVELS[j]:
            return Vec.from_numpy(X[:, j], type=T_CAT, domain=domain_of(j))
        return Vec.from_numpy(X[:, j])

    # a column's packing is NumPy passes over its rows: a few threads wide
    with ThreadPoolExecutor(5) as pool:
        vecs = list(pool.map(vec, range(X.shape[1] + 1)))
    fr = Frame(feature_names(X.shape[1]) + [LABEL], vecs)
    jax.block_until_ready([v.data for v in fr.vecs])
    return fr

"""warm_compile_s — seconds the program spent building executables during
set-up (`h2o3_xla_compile_seconds_total`, which also times loads from the
persistent cache). In a run served from the cache this is what a warm
start still pays; in a checkout's first run it is the compilation."""


def read(rec):
    if rec["rehearse"]:
        return None
    return rec["setup_counters"]["compile_s"]

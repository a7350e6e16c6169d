"""Programs XLA compiled inside the window: jax's own count of backend
compile requests minus those its persistent cache served. The guard that
nothing compiles in the measured window: every statement was rehearsed, so
this reads 0, and a change that makes the window build programs again
shows here before it shows in `replay_qps`."""

LAYER = "compile caches"
UNIT = "count"
MOVES = "replay_qps"
SOURCE = "program_counter"

_REQUESTS = "/jax/core/compile/backend_compile_duration"
_SERVED = "/jax/compilation_cache/cache_hits"


def read(run):
    a = run["counters"]["rehearsal_end"]["jax"]
    b = run["counters"]["window_close"]["jax"]
    if _REQUESTS not in b:
        return 0

    def count(snap, key):
        return snap.get(key, [0, 0.0])[0]

    return (count(b, _REQUESTS) - count(a, _REQUESTS)) - (
        count(b, _SERVED) - count(a, _SERVED))

"""Round program: ``memory_stats()["peak_bytes_reserved"]`` of the first
chip after the window, in GB (1e9 bytes).  On this runtime it is the key
that includes the round program's temporaries (PERF.md section 5)."""


def read(r):
    reserved = r.memory[0].get("peak_bytes_reserved") if r.memory else None
    return reserved / 1e9 if reserved else None

"""JIT compilations begun inside the measured engine's ticks (jaxpr
traces and backend compiles, counted by the program through
``jax.monitoring``), from the engine's own record.  Every shape the
window uses is warmed in set-up, so a sound run reads 0; each one a tick
stalls that tick for the compile.
"""

from bench import engine_record


def read(ctx):
    snap = engine_record.snapshot()
    if snap is None:
        return None
    return float(sum(snap["ticks"]["compiles"]))

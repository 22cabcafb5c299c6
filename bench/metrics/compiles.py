"""compiles: programs compiled inside the measured window.

Counter of ``core/compile_cache.py`` (a ``jax.monitoring`` listener): the
change of ``stats()["requests"]``, one per program that missed JAX's
in-memory cache, between the window's start and its close. Warm-up
should leave it at 0; each compile stalls the pump for its length.
"""


def read(ctx):
    c0, c1 = ctx.compiles
    return float(c1["requests"] - c0["requests"])

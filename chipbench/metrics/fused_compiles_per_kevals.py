"""Executables the window's jit calls had to obtain, per 1000 SA evals:
the ``/jax/core/compile/backend_compile_duration`` events a
``jax.monitoring`` listener counts, which JAX records for every compile
request, whether the persistent cache then serves it (``cache_loads``) or
the backend compiles it.  Each new evaluator jits its own fused closure,
so a sweep pays at least one per candidate and (B, padded length) pair."""


def read(run):
    evals = run.obs.get("evals")
    if not evals or "compiles" not in run.obs:
        return None
    return run.obs["compiles"] * 1e3 / evals

"""Executables the window's jit calls had to obtain, per 1000 SA evals:
the ``/jax/core/compile/backend_compile_duration`` events a
``jax.monitoring`` listener counts, which JAX records for every compile
request, whether the persistent cache then serves it (``cache_loads``) or
the backend compiles it.  Every evaluator binds its candidate to the one
fused program the process shares, so a sweep pays one only for a (batch
bucket, ``buf_len``, padded length) that no earlier call of the process
met, whatever the candidate."""


def read(run):
    evals = run.obs.get("evals")
    if not evals or "compiles" not in run.obs:
        return None
    return run.obs["compiles"] * 1e3 / evals

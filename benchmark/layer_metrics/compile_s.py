"""Seconds JAX spent in backend compiles (or loading them from the persistent cache) during set-up; from `jax.monitoring`."""


def read(facts):
    return facts["compile"]["backend_compile_s"]

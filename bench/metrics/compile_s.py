"""compile_s: seconds JAX spent compiling or loading compiled programs during
set-up (jax.monitoring compile events)."""


def read(rec):
    return rec["setup"]["compile_s"]

"""Seconds the set-up spent compiling or loading compiled programs from
the persistent cache (JAX's ``backend_compile_duration`` events)."""


def read(rec):
    return rec["setup"]["compile_s"]

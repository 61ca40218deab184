from repro.data import trajgen  # noqa: F401

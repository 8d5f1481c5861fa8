"""Executables the engine compiled inside the window (its
``compile_count`` delta); 0 when warm-up covered every shape."""


def read(record):
    return record.get("compiles_in_window")

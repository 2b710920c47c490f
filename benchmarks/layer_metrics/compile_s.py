"""``compile_s``: seconds spent compiling, or loading compiled programs from
the persistent cache, before the window opens.

Layer: CLI / compile. Source: JAX's own ``backend_compile_duration`` events,
heard by the harness's listener (a cache hit fires it with the retrieval
time), which cover the program's ``gordo_fleet_compile_seconds`` too. Moves
``setup_s``. Never 0: a warm run still loads its programs.
"""


def read(view):
    return view["compile_before"]["seconds"] or None

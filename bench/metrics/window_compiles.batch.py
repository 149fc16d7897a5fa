"""Device / compile: XLA programs built inside the window, compiled or
loaded from the persistent cache (``backend_compile_duration`` events)."""


def read(run):
    return run.window_compiles

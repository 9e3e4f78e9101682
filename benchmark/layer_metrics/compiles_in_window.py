"""set-up: programs compiled, or fetched from the cache, inside the measured
window. Must read 0: every shape is warmed before it."""


def read(artifacts):
    compile_ = artifacts.get("compile")
    return compile_["compiles_in_window"] if compile_ else None

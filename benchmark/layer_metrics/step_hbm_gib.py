"""device: bytes per device the cell's step program needs, arguments plus
temporaries, from the compiler's own analysis of the compiled step."""


def read(artifacts):
    memory = artifacts.get("step_memory")
    if not memory:
        return None
    return (memory["argument_bytes"] + memory["temp_bytes"]) / 2 ** 30

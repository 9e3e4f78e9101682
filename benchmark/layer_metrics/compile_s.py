"""set-up: seconds this process spent in backend compiles or in fetching
programs from the persistent cache (``jax.monitoring``)."""


def read(artifacts):
    compile_ = artifacts.get("compile")
    return compile_["compile_s"] if compile_ else None

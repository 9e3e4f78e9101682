"""model: the share of a diffusion step's tokens that were masked — the
program's own counter ``diffusion_masked_share`` as the loss reported it on the
check's sequences (the steady driver keeps a step's loss alone, so not the
window's mean): 50 under a time uniform in (0, 1); nothing where the program
reports no such counter."""


def read(artifacts):
    share = artifacts.get("check", {}).get("counters", {}).get(
        "diffusion_masked_share")
    return None if share is None else 100.0 * share

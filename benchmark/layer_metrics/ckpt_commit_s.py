"""checkpoint: from the record of a saving step to that checkpoint's
``COMMITTED`` marker on disk — of the newest save that committed (the kill
beats S1, so that is C0, the save the resume then restores)."""

from lib import timeline_reduce as tl


def read(artifacts):
    if "commits" not in artifacts:
        return None
    done = [s for s in artifacts["save_steps"]
            if str(s) in artifacts["commits"]]
    if not done:
        return None
    return tl.commit_s(artifacts["records"], artifacts["commits"], done[-1])

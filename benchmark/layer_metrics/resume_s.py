"""elastic control: seconds from the SIGKILL of the worker to the first step
record of the next generation. Host clock (the driver's and the worker's
``time.time()`` on one host). One sample a run, and the host's share of it
(TPU runtime start, reaping the killed process) spreads too widely for a
bound: recorded, not judged."""

from lib import timeline_reduce as tl


def read(artifacts):
    if artifacts.get("t_kill") is None:
        return None
    return tl.resume_s(artifacts["records"], artifacts["t_kill"],
                       artifacts["killed_generation"])

"""Seconds from the SIGKILL of the worker to the first step record of the
generation that resumes: reaping the killed process, the master's decision,
the new process up to its trainer, the restore of the newest committed
checkpoint (under another mesh where the mix names one) and the first step.
Host clock (the driver's and the worker's ``time.time()`` on one host). An
end-to-end metric where the mix puts the resume into set-up, one resume a
run; the first run of a checkout compiles the resumed generation's step
program inside it."""

from lib import timeline_reduce as tl


def read(artifacts):
    if artifacts.get("t_kill") is None:
        return None
    return tl.resume_s(artifacts["records"], artifacts["t_kill"],
                       artifacts["killed_generation"])

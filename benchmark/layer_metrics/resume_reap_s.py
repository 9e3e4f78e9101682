"""elastic control: SIGKILL to the agent's ``worker_crash`` — how long the killed
process took to be reaped (seconds for one that held a TPU). With
``resume_decide_s`` it is ``resume_detect_s``."""

from lib import phase_records


def read(artifacts):
    return phase_records.reap_s(artifacts)

"""elastic control: ``resume_reap_s`` where the resume lies in set-up and
``recovery_s`` is what it moves — SIGKILL to the agent's ``worker_crash``: how
long the killed process took to be reaped (a quarter of a minute for one
that held four chips)."""

from lib import phase_records


def read(artifacts):
    return phase_records.reap_s(artifacts)

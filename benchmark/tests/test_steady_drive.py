"""The steady driver's loop alone, on a trainer that counts: how far it runs
ahead of the loss it waits for, that every step sent is waited for before the
clock is read, and that nothing is sent once the time is up."""

import importlib.util
import os

import pytest

from conftest import BENCH

spec = importlib.util.spec_from_file_location(
    "steady_driver", os.path.join(BENCH, "drivers", "steady.py"))
steady = importlib.util.module_from_spec(spec)
spec.loader.exec_module(steady)


class Loss:
    """A step's loss: fetching it ends the step (and every one before it)."""

    def __init__(self, trainer, number):
        self.trainer, self.number = trainer, number

    def __float__(self):
        self.trainer.done = max(self.trainer.done, self.number + 1)
        return float(self.number)


class Counting:
    def __init__(self):
        self.sent = self.done = self.most_in_flight = 0

    def train_step(self, state, batch):
        assert batch == self.sent  # the batches in their order, none skipped
        self.sent += 1
        self.most_in_flight = max(self.most_in_flight, self.sent - self.done)
        return state + 1, {"loss": Loss(self, self.sent - 1)}


def batches():
    n = 0
    while True:
        yield n
        n += 1


@pytest.mark.parametrize("ahead", [0, 1, 3, 24])
def test_drive_runs_ahead_by_what_the_mix_says_and_waits_for_all(ahead):
    trainer, left = Counting(), iter(range(40))
    state, losses, arrived = steady.drive(
        trainer, 0, batches(), ahead, lambda: next(left, None) is not None)
    assert state == 40 and trainer.sent == trainer.done == 40
    assert losses == [float(i) for i in range(40)]  # each, in its order
    assert len(arrived) == 40 and arrived == sorted(arrived)
    # the step awaited and ``ahead`` beyond it: a closed loop at 0
    assert trainer.most_in_flight == ahead + 1


def test_drive_sends_nothing_once_the_time_is_up():
    trainer = Counting()
    state, losses, arrived = steady.drive(
        trainer, 0, batches(), 5, lambda: trainer.sent < 7)
    assert trainer.sent == 7 and len(losses) == len(arrived) == 7


def test_drive_names_the_hosts_three_parts_in_a_traced_run():
    import contextlib
    seen = []

    @contextlib.contextmanager
    def span(name):
        seen.append(name)
        yield

    left = iter(range(2))
    steady.drive(Counting(), 0, batches(), 0,
                 lambda: next(left, None) is not None, span)
    assert seen == ["bench/next_data", "bench/dispatch",
                    "bench/fetch_loss"] * 2

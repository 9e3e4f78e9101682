"""The benchmark's own yardstick: FLOP counts, peaks, the plain reference,
the reduction from traces, timelines and step records to numbers. Later PRs
may add files here and may not edit one that is there."""

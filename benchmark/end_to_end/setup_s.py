"""Process start to window open: loading, the reference check, compilation
(or the compile cache's answers), warm-up — and, in a cell with a job, its
training up to the first committed checkpoint. Host clock."""


def read(artifacts):
    return artifacts["setup_s"]

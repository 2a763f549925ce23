"""Staged-plan tiers executed per chunk (``StageReport.ran``); each is one
host round trip for the undecided rows."""


def read(run):
    if not run.rec.chunks:
        return None
    return sum(len(c.ran) for c in run.rec.chunks) / len(run.rec.chunks)

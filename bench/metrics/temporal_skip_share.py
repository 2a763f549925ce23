"""Share of the fleet's frames the temporal tier skipped outright because
every query's window outcome was decided (``TemporalStats``)."""


def read(run):
    t = run.temporal
    if t is None or not t["frames_in"]:
        return None
    return 100.0 * t["frames_skipped"] / t["frames_in"]

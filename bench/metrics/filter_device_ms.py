"""Device time of the served filter step (the jitted ``filter_step``,
trunk prefix plus branch head) per frame, from the trace."""


def read(run):
    if run.trace is None:
        return None
    calls = run.trace.module_count("jit_filter_step")
    if not calls:
        return None
    return run.trace.module_s("jit_filter_step") / (calls * run.batch) * 1e3

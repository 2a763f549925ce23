"""The first device's idle milliseconds per chunk whose innermost open
span, of the benchmark's and the program's, is a program (``repro.``)
span: idle time the program's own host work holds the chip back
(``bench/program_spans.py``)."""
from bench import program_spans as PS


def read(run):
    if run.trace is None:
        return None
    return PS.engine_idle_ms(run.trace, run.trace.program,
                             len(run.rec.chunks))

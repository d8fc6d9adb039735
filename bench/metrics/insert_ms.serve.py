"""Engine, insert stage (``StagedEngine``'s ``_insert_fn`` program: a
finished prefill moved into its decode slot, or a slot's row cleared as a
request takes it): mean device time of its program executions in the
window, in ms.  Nothing to read where no program carries that name."""
from bench import program_trace as P


def read(r):
    return P.insert_ms(r.red)

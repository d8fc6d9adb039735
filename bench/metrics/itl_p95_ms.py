"""Gap between output tokens, 95th percentile over every gap between two
consecutive tokens of one request whose later token came inside the
window (the load generator stamps each token after the step that made it)."""
from bench.readings import nearest_rank


def read(r):
    w = r.window
    gaps = [(b - a) * 1e3 for x in w.records
            for a, b in zip(x.token_t, x.token_t[1:]) if w.t0 < b <= w.t1]
    return nearest_rank(gaps, 95) if gaps else None

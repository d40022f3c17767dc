"""Output tokens the host read in the window, of every request, finished
or not, over the window's length (host clock)."""


def read(run):
    n = sum(len(r.token_times) for r in run.reqs.values())
    t0, t1 = run.window
    return n / (t1 - t0)

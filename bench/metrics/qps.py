"""qps: queries answered in the window over the window's time.

The window's time runs from its opening to the last answer of a request
sent in it: the clients stop sending at the close and every request
still in flight is answered, so every request of the window and all the
time it took count, and no batch is split at the close."""


def read(run):
    done = [r.t_done for r in run.requests if r.ok]
    if not done:
        return None
    return len(done) / (max(done) - run.start)

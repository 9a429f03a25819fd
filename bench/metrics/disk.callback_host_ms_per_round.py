"""disk.callback_host_ms_per_round: host time of the disk tier's callback
bodies (the ``disk.submit``, ``disk.drain`` and ``disk.fetch`` spans)
in the traced part of the window, per round of the search loop in it.

Rounds are counted as in ``bench/stages.py``'s ``traced_rounds``.
Nothing to read without a trace, without a disk tier, or from a program
whose spans do not cover whole callbacks (no ``disk.drain`` or
``disk.fetch`` span)."""
from bench import stages


def read(run):
    if run.trace is None or run.config["record_tier"]["tier"] != "disk":
        return None
    spans = run.trace["spans"]
    if "disk.drain" not in spans and "disk.fetch" not in spans:
        return None
    rounds = stages.traced_rounds(run.calls, run.trace["t0"], run.trace["t1"])
    if rounds <= 0:
        return None
    return 1e3 * sum(spans.get(k, 0.0) for k in stages.CALLBACK_SPANS) / rounds

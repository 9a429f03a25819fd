"""disk.drain_wait_ms_per_query: time the search loop's ordered callback
waited for the disk tier's reads (``disk.drain_wait`` spans), summed
over the traced part of the window, per query answered in it.  Nothing
to read without a trace or without a disk tier."""


def read(run):
    if run.trace is None or run.config["record_tier"]["tier"] != "disk":
        return None
    if not run.trace["answered"]:
        return None
    return 1e3 * run.trace["spans"].get("disk.drain_wait", 0.0) / run.trace["answered"]

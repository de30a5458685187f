"""Seconds per job to send X and Y until both are resident (client
clock), averaged over the jobs of the window."""


def read(run):
    sends = [c.answer["send_s"] for c in run.calls
             if c.request["kind"] == "cg_job" and c.answer is not None]
    return sum(sends) / len(sends) if sends else None

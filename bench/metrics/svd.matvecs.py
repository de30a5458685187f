"""Gram matvecs per truncated SVD, as the routine reports them."""


def read(run):
    counts = [c.answer["matvecs"] for c in run.calls
              if c.request["kind"] == "svd" and c.answer is not None]
    return sum(counts) / len(counts) if counts else None

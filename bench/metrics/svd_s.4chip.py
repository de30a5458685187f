"""``svd_s`` in the four-chip cell: seconds per truncated SVD over the
whole window (client clock). The cell's runs spread more than half of
``svd_s``'s bound, so it has a bound of its own (PERF.md, section 2)."""
from harness.spec import reader_of

read = reader_of("svd_s")

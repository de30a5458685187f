"""``svd.matvec_roofline``, read in the four-chip cell, where it moves
``svd_s.4chip``."""
from harness.spec import reader_of

read = reader_of("svd.matvec_roofline")

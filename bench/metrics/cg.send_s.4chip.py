"""``cg.send_s``, read in the four-chip cell under a name of its own, so
that one chip's readings and four chips' stay apart."""
from harness.spec import reader_of

read = reader_of("cg.send_s")

"""The tiny size of the SpMM configuration's structure, for the tests
that cut every configuration of the spec to a CPU size
(`tiny.tiny_spec`)."""
from bench.tests import tiny

tiny.TINY_LOG2_ROWS.setdefault("kron_gcn", 10)

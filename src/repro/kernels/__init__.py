"""Pallas TPU kernels: compiled by Mosaic on a TPU; on a CPU host they
run only in interpret mode (``interpret=True``), as the tests do."""
from . import bfm, sbm_sweep, ops, ref

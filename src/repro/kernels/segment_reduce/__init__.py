from . import ops, ref
from .segment_reduce import run_scan_pallas

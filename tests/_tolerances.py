"""Tolerances shared by more than one test module."""

# int8 end-to-end envelope: per-kernel q8 bounds (repro.sparse.quantize)
# do not compose through a model's nonlinearities, so a q8 model forward,
# offline or served, is held to this measured envelope (DESIGN.md §12)
Q8_E2E_TOL = 0.05

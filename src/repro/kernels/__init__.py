# Pallas TPU kernels for the framework's compute hot-spots (the paper's own
# contribution is scheduling/prediction — these serve the model zoo):
#   flash_attention.py  flash attention forward + dQ, dK/dV backward kernels
#                       (causal/GQA/window)
#   rglru_scan.py       chunked RG-LRU linear recurrence
#   ops.py              jit'd wrappers with custom VJPs
#   ref.py              pure-jnp oracles (correctness ground truth)
from . import ref
from .ops import flash_attention, rglru_scan

__all__ = ["flash_attention", "rglru_scan", "ref"]

"""Pallas TPU kernels for the compute hot spots (DESIGN.md §5).

| kernel            | hot spot                                                |
|-------------------|---------------------------------------------------------|
| ``frame_accum``   | Θ(T·n) state-frame accumulation (Alg. 2 line 27)        |
| ``bfs_frontier``  | one BFS level of SAMPLE() (CSR frontier expansion)      |
| ``flash_attention``| prefill/train attention with causal/window block skip  |
| ``ssm_scan``      | Mamba selective-scan recurrence                         |
| ``rglru_scan``    | RG-LRU gated linear recurrence                          |

``ops.py`` exposes wrappers that run each kernel compiled on a TPU and its
pure-jnp oracle elsewhere; ``ref.py`` holds those oracles, which every kernel
is tested against (``interpret=True``) across shape/dtype sweeps.
"""
from . import ops, ref  # noqa: F401

__all__ = ["ops", "ref"]

"""Pallas TPU kernels for the paper's linear-algebra primitive classes.

  affine          -- vector-vector + vector-scalar (translation/scaling, 5.1-5.2)
  rope            -- rotation transform on head-dim pairs (5.3)
  matmul          -- tiled MXU matmul (rotation/composite, 5.3)
  rmsnorm         -- derived-scalar scaling fusion (beyond paper)
  flash_attention -- streaming composite transform (beyond paper)
  ssd             -- Mamba-2 intra-chunk core, VMEM-resident (beyond paper)

Composite-chain lowering targets (the paper's one-pass "General Composite
Algorithm"): ``chain_diag`` (folded diagonal chains, VPU-only) and
``chain_apply`` (folded general chains, lane-rolled q = p @ A + t); both
are single-HBM-pass kernels over the flattened point buffer and are what
``repro.core.transform_chain`` compiles to.  ``chain_project`` extends
the family to *projective* plans (homogeneous viewing chains with an
in-kernel perspective divide + frustum-cull mask -- the graphics
companion paper's 2D/3D pipelines).  The batched forms
``chain_diag_batch`` / ``chain_apply_batch`` / ``chain_project_batch``
take a packed (B, L, d) request batch with per-request folded parameters
and are what ``repro.serving`` lowers a whole plan bucket to -- one
launch per bucket.  ``chain_project_instanced`` takes one resident point
buffer under B folded chains: the instances of a mesh uploaded once.

The fixed-point lane (``kernels.fixedpoint``) re-expresses the chain
family on the M1's int16 Qm.n datapath: ``chain_diag_q`` /
``chain_apply_q`` (+ batch forms) run int32-accumulate MACs with a
single requantising shift over int16 point buffers -- half the HBM
bytes per point -- and are what quantised ``TransformChain`` plans
(``dtype="q8.7"``) and serving buckets lower to.  Projective plans have
no fixed-point form (the in-kernel divide stays float).

Every family ships ``ops.py`` (public entry, backend-dispatched) and
``ref.py`` (pure-jnp oracle).  See ``repro.kernels.dispatch``; HBM byte
accounting for perf tests lives in ``repro.kernels.opcount``.
"""
from repro.kernels import dispatch, opcount
from repro.kernels.affine import (affine, chain_diag, chain_diag_batch, scale,
                                  translate, vecadd)
from repro.kernels.fixedpoint import (chain_apply_batch_q, chain_apply_q,
                                      chain_diag_batch_q, chain_diag_q)
from repro.kernels.flash_attention import attention, blockwise_attention
from repro.kernels.matmul import chain_apply, chain_apply_batch, matmul, rotate2d
from repro.kernels.projective import (chain_project, chain_project_batch,
                                      chain_project_instanced)
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.rope import rope, rope_tables
from repro.kernels.ssd import ssd_intra

__all__ = [
    "dispatch", "opcount", "affine", "chain_diag", "chain_diag_batch",
    "scale", "translate", "vecadd", "attention", "blockwise_attention",
    "chain_apply", "chain_apply_batch", "chain_apply_batch_q",
    "chain_apply_q", "chain_diag_batch_q", "chain_diag_q", "chain_project",
    "chain_project_batch", "chain_project_instanced", "matmul", "rotate2d", "rmsnorm",
    "rope", "rope_tables", "ssd_intra",
]

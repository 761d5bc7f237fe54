from repro.kernels.projective.ops import (chain_project, chain_project_batch,
                                          chain_project_instanced)

__all__ = ["chain_project", "chain_project_batch", "chain_project_instanced"]

from repro.kernels.moe_gmm.kernel import held_experts_gmm, held_experts_tgmm
from repro.kernels.moe_gmm.ops import grouped_matmul

"""Paper evaluation workloads as GEMM-sequence Tasks (Sec. 7): AlexNet,
ViT, Vision Mamba, HydraNet, plus DeepSeek-V2's latent attention and
unevenly routed experts."""
from .alexnet import alexnet_task  # noqa: F401
from .deepseek_v2 import deepseek_v2_task  # noqa: F401
from .hydranet import hydranet_task  # noqa: F401
from .vision_mamba import vision_mamba_task  # noqa: F401
from .vit import vit_task  # noqa: F401

WORKLOADS = {
    "alexnet": alexnet_task,
    "vit": vit_task,
    "vision_mamba": vision_mamba_task,
    "hydranet": hydranet_task,
    "deepseek_v2": deepseek_v2_task,
}

#: The paper's evaluation workloads (Sec. 7), which its figures sweep.
PAPER_WORKLOADS = ("alexnet", "vit", "vision_mamba", "hydranet")

"""paddle_tpu.ops.pallas — fused TPU kernels (Pallas) and their gates.

Public face of the kernel tier: callers import entry points from HERE
instead of deep-importing the implementation modules. Every routed entry
consults a gate (platform ``"tpu"`` and an eligible shape) ONCE: where the
gate selects the Pallas kernel it runs, and a failure raises; elsewhere the
gate selects the XLA formulation, which is also the reference the tests and
``chip_smoke.py`` compare the kernel against. ``fused_adamw_flat`` has no
XLA twin: it compiles for a TPU, or runs through the Pallas interpreter when
a test asks for it. The ``KERNELS`` manifest records, per kernel, the entry
point, the gate that decides the fused path, and the module holding the
implementation — introspection for tooling and tests.

Note the package attributes ``flash_attention`` / ``fused_adamw`` /
``fused_rms_norm`` remain the implementation MODULES (several callers
reach module state through them, e.g. ``FLAGS_use_flash_attention`` →
``flash_attention._FLASH_ENABLED``); the canonical entry CALLABLES are the
non-colliding names re-exported below and the ``entry`` field of
``KERNELS``.
"""

from paddle_tpu.ops.pallas.flash_attention import (  # noqa: F401
    flash_attention_fwd,
    flash_attn_unpadded,
    scaled_dot_product_attention,
)
from paddle_tpu.ops.pallas.fused_adamw import (  # noqa: F401
    fused_adamw_flat,
    pad_flat,
    use_fused_adamw,
)
from paddle_tpu.ops.pallas.fused_rms_norm import (  # noqa: F401
    rms_norm_pallas,
    rms_norm_routed,
    use_fused_rms_norm,
)

# the submodules themselves (imported above) stay addressable: package
# attrs flash_attention / fused_adamw / fused_rms_norm are the modules
from paddle_tpu.ops.pallas import (  # noqa: F401  (self-imports for clarity)
    flash_attention,
    fused_adamw,
    fused_rms_norm,
)

#: kernel id -> {entry, gate, module}: ``entry`` is the callable, ``gate``
#: returns whether the fused Pallas path is taken (None = decided per-call
#: on shape/platform inside the entry), ``module`` holds the implementation
#: + its reference lowering.
KERNELS = {
    "flash_attention": {
        "entry": flash_attention.flash_attention,
        "gate": None,   # per-call: shape/head-dim/platform inside the entry
        "module": "paddle_tpu.ops.pallas.flash_attention",
    },
    "fused_adamw": {
        "entry": fused_adamw_flat,
        "gate": use_fused_adamw,
        "module": "paddle_tpu.ops.pallas.fused_adamw",
    },
    "fused_rms_norm": {
        "entry": rms_norm_routed,
        "gate": use_fused_rms_norm,
        "module": "paddle_tpu.ops.pallas.fused_rms_norm",
    },
}

__all__ = [
    "KERNELS",
    "flash_attention",
    "flash_attention_fwd",
    "flash_attn_unpadded",
    "fused_adamw",
    "fused_adamw_flat",
    "fused_rms_norm",
    "pad_flat",
    "rms_norm_pallas",
    "rms_norm_routed",
    "scaled_dot_product_attention",
    "use_fused_adamw",
    "use_fused_rms_norm",
]

# Production kernel layer (DESIGN.md §3): the fused GradES monitor
# (grades_norm), frozen-gated optimizer updates (masked_adamw/masked_sgd),
# flash attention and the sLSTM scan, with pure-jnp oracles in ref.py and the
# backend-aware routing in dispatch.py (pallas | jnp | auto).  The train step
# reaches these through repro.kernels.dispatch, never directly.
import collections
import re
from typing import Optional

import jax

#: the ``name`` of every pallas_call (megablox's grouped matmuls, which the
#: held experts run, are named after their functions ``gmm`` and ``tgmm``);
#: compiled HLO names each Mosaic custom call after it, with transformation
#: prefixes such as ``jvp_``/``transpose_``
KERNEL_NAMES = ("grades_norm", "masked_adamw", "masked_sgd", "flash_fwd",
                "flash_dq", "flash_dkv", "paged_decode", "slstm", "tgmm",
                "gmm")
_CUSTOM_CALL = re.compile(
    r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"')


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Pallas interpret mode for a kernel entry point: an explicit choice
    wins; ``None`` means compiled kernels on a TPU backend and the
    interpreter anywhere else, so a caller that leaves it out never runs
    the emulation on the chip."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def compiled_kernels(hlo_text: str) -> collections.Counter:
    """Pallas kernels in a compiled TPU program's HLO text, counted by name
    (a custom call that matches no known name counts under its own)."""
    return collections.Counter(
        next((k for k in KERNEL_NAMES if k in name), name)
        for name in _CUSTOM_CALL.findall(hlo_text))

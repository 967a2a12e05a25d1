"""The causal flash-attention kernels (``kernels/flash_attention``), as
the compiled program names them.

Every one of them (forward, dK/dV, dQ) is a ``pallas_call`` named
``gqa_causal_*``, and its compiled body holds that name as the Mosaic
module's name; the fused update's body does not. (A body also holds the
Python function names of the frames that traced it, which can be
another kernel's: the name is no function's.) Their operations under causal block skipping
are not counted here yet, so they have no roofline.
"""
from __future__ import annotations

# in every attention kernel's compiled body, and in no other kernel's
MARK = b"gqa_causal_"

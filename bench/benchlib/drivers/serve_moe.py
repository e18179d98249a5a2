"""Driver for an MoE model with latent attention served from coded storage:
``serve``'s closed loop of rounds and its check, with the DeepSeek-V3-family
reference (``benchlib.mla_moe_lm``) in place of the dense one.

The round loop, the warm-up, the traced window and the check are those of
``drivers/serve.py``, run from a module object of this driver's own whose
``dense_lm`` is ``mla_moe_lm``: sizes, the program's configuration, the
seeded weights, the float32 reference and its control all come from there.
So does the widest gap, which leaves out the positions whose routing the
reference puts within ``limits.route_margin`` of a change of held experts
(``mla_moe_lm.reference_logits``): there a model that rounds to bfloat16
may route otherwise, and a swapped expert moves the logits as far as the
fp8 control's rounding does.
"""

from __future__ import annotations

from benchlib import manifest, mla_moe_lm

_serve = manifest.driver("serve")
_serve.dense_lm = mla_moe_lm

run = _serve.run

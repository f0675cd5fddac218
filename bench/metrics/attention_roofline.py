"""The roofline time of the attention the traced slice's steps need (the
family's `attention_work`: causal over the real context, Q, the context's
K/V and the output read or written once, bf16) over the device time of the
ops under `pattn.*` scopes, in percent (`bench.scopes`)."""
from bench.scopes import attention_roofline as read  # noqa: F401

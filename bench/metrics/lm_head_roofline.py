"""The roofline time of the LM-head GEMMs for the rows whose logits the
traced slice's steps use (those the family's `step_gemms` tags `lm_head`)
over the device time of the ops under the `lm_head` scope, in percent
(`bench.scopes`)."""
from bench.scopes import lm_head_roofline as read  # noqa: F401

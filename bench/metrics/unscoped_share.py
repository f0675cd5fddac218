"""Device time of the traced slice's ops under none of the serve step's
top-level scopes (the layer scan's slicing, XLA's copies, the harness's
argmax) over the busy time, in percent (`bench.scopes`)."""
from bench.scopes import unscoped_share as read  # noqa: F401

"""Steps completed in the window times the tokens of a step, over the
seconds to the end of the last step (each step ended by block_until_ready)."""

from ..layer_metrics._common import train_tok_s as read  # noqa: F401

"""``step_mfu`` in a cell judged on output tokens per second."""
from metrics_common import load_sibling

read = load_sibling("step_mfu").read

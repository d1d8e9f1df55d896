"""``paged_attn_roofline`` in a cell judged on output tokens per second."""
from metrics_common import load_sibling

read = load_sibling("paged_attn_roofline").read

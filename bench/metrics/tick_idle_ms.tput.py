"""``tick_idle_ms`` in a cell judged on output tokens per second."""
from metrics_common import load_sibling

read = load_sibling("tick_idle_ms").read

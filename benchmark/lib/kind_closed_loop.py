"""Traffic kind ``closed_loop``: see lib/serving.py (the server side is the same
for both serving kinds; lib/client.py holds the two generators)."""

from benchmark.lib.serving import run  # noqa: F401

"""``device_idle_pct``'s reading, for the cells whose tail latency it
moves: a metric moves one end-to-end metric, so each gets a name."""
import os

from bench.run import load_module

_ROOT = os.path.abspath(os.path.join(__file__, "..", "..", ".."))
read = load_module(_ROOT, "layers", "device_idle_pct").read

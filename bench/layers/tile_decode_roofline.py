"""The decode kernel's share of its roofline on a tiled layout, in %:
``decode_roofline``'s reading at the cell's decode shape, which the
loop gives as one tile's GOP (``ctx.gop_shape``). Every device op of
the window serves the tile decodes, so the passes around the kernel
count against it."""
import os

from bench.run import load_module

_ROOT = os.path.abspath(os.path.join(__file__, "..", "..", ".."))
read = load_module(_ROOT, "layers", "decode_roofline").read

"""The benchmark's own tests run on the CPU; nothing here needs a chip."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"

# a run at this size: every cell's operator cut to 16^3
SMALL = {"nx": 16, "ny": 16, "nz": 16}

import os
import sys

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# the service's GP state is float64, as in every entry point
jax.config.update("jax_enable_x64", True)

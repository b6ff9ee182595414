"""The benchmark's own tests run on the CPU, in seconds:

    python3 -m pytest perfbench/tests -q -p no:cacheprovider
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import os
import sys

# The benchmark's own code lives in bench/ (benchkit, drivers, ...).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "bench"))

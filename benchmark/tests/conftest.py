import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(BENCH, "generators"), os.path.dirname(BENCH),
                os.path.dirname(os.path.abspath(__file__))]

import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(os.path.dirname(_HERE))
sys.path.insert(0, os.path.join(_ROOT, "benchmarks", "chip"))
sys.path.insert(0, os.path.join(_ROOT, "src"))

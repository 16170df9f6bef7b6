import os
import sys

# tests see the single real CPU device (the spmd subprocess tests set
# their own device-count flags)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

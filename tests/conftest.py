import os
import sys

# Tests see the real single-CPU device; a test that needs more host devices
# runs them in a child process.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

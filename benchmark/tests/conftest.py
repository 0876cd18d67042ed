import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

# the tests' compiled programs stay out of the checkout's cache
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(
    tempfile.gettempdir(), "bench-tests-jax-cache"))

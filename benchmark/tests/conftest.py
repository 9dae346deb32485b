import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

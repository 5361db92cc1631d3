import os

# the rehearsals run on the CPU; the benchmark itself refuses it
os.environ.setdefault("JAX_PLATFORMS", "cpu")

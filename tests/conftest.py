"""The tests run on the CPU (interpret-mode kernels, host-device meshes),
also on a host with a chip: each xdist worker is a process of its own,
and a chip belongs to one process."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"

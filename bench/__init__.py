"""The chip benchmark of the kernel machine: ``python bench/run.py``."""

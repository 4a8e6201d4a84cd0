"""Traffic of kind ``serve_open``: see serve.py."""
from .serve import run  # noqa: F401

"""Traffic of kind ``serve_closed``: see serve.py."""
from .serve import run  # noqa: F401

from .ops import ivf_scan

__all__ = ["ivf_scan"]

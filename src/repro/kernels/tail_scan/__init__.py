from .kernel import ROW_BLOCK, tail_scan_pallas

__all__ = ["ROW_BLOCK", "tail_scan_pallas"]

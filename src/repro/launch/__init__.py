from repro.launch import mesh

__all__ = ["mesh"]

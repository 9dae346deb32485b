from .profiling import DecodeStats, Timer, trace

__all__ = ["DecodeStats", "Timer", "trace"]

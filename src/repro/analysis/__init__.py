"""Compiled-artifact analysis: scan-aware HLO collective and traffic
profiling."""
from .hlo import (CollectiveStats, HLOProfile, parse_collectives,
                  profile_module)

__all__ = ["CollectiveStats", "HLOProfile", "parse_collectives",
           "profile_module"]

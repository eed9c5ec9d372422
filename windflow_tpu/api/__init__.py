"""Composition layer (L4): MultiPipe + the 16 fluent builders — the
equivalents of the reference's multipipe.hpp and builders.hpp — and the
builder of the window join, which the reference lacks."""

from .builders import (LEVEL0, LEVEL1, LEVEL2, Accumulator_Builder,
                       Filter_Builder, FlatMap_Builder, KeyFarm_Builder,
                       KeyFarmTPU_Builder, Map_Builder, PaneFarm_Builder,
                       PaneFarmTPU_Builder, Sink_Builder, Source_Builder,
                       WinFarm_Builder, WinFarmTPU_Builder,
                       WinJoinTPU_Builder, WinMapReduce_Builder,
                       WinMapReduceTPU_Builder,
                       WinSeq_Builder, WinSeqTPU_Builder)
from .multipipe import MultiPipe, union_multipipes

__all__ = [
    "MultiPipe", "union_multipipes",
    "Source_Builder", "Filter_Builder", "Map_Builder", "FlatMap_Builder",
    "Accumulator_Builder", "Sink_Builder",
    "WinSeq_Builder", "WinFarm_Builder", "KeyFarm_Builder",
    "PaneFarm_Builder", "WinMapReduce_Builder",
    "WinSeqTPU_Builder", "WinFarmTPU_Builder", "KeyFarmTPU_Builder",
    "PaneFarmTPU_Builder", "WinMapReduceTPU_Builder", "WinJoinTPU_Builder",
    "LEVEL0", "LEVEL1", "LEVEL2",
]

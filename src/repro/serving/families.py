"""The measure families the engines serve, by name.

``MEASURES[name]`` is a family's ``core.engine.Measure`` and
``ENGINES[name]`` the engine class that serves it; ``name`` is what the
fleet's ``mode``, a snapshot's ``meta["mode"]`` and telemetry's
``engine=`` label carry. Callers that handle either family look it up
here instead of testing which one they hold.
"""
from __future__ import annotations

from repro.regression.engine import RegressionServingEngine
from repro.serving.engine import ServingEngine

ENGINES = {cls.measure.name: cls
           for cls in (ServingEngine, RegressionServingEngine)}
MEASURES = {name: cls.measure for name, cls in ENGINES.items()}


__all__ = ["ENGINES", "MEASURES"]

"""SplitFS-on-TPU: split-architecture storage plane for JAX training/serving.

See DESIGN.md (system inventory + paper mapping) and EXPERIMENTS.md
(validation, dry-run, roofline, perf log)."""

__version__ = "1.0.0"

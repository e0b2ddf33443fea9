"""Distribution layer: logical-axis sharding rules (DESIGN.md §3)."""

from repro.dist.sharding import (AxisRule, AxisRules, RULES_LONG, RULES_SERVE,
                                 RULES_TRAIN, ambient_mesh, constrain,
                                 logical_to_spec, sanitize_spec,
                                 tree_shardings)

__all__ = [
    "AxisRule", "AxisRules", "RULES_LONG", "RULES_SERVE", "RULES_TRAIN",
    "ambient_mesh", "constrain", "logical_to_spec", "sanitize_spec",
    "tree_shardings",
]

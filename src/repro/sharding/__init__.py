from repro.sharding.logical import (
    LogicalRules,
    resolve_spec,
    logical_constraint,
    use_rules,
)

__all__ = [
    "LogicalRules",
    "resolve_spec",
    "logical_constraint",
    "use_rules",
]

"""Framework core: Program IR, registry, executor, backward, scope."""

from . import unique_name  # noqa: F401
from .program import (  # noqa: F401
    Block,
    Operator,
    Parameter,
    Program,
    Variable,
    default_main_program,
    default_startup_program,
    device_guard,
    name_scope,
    grad_var_name,
    in_dygraph_mode,
    program_guard,
)
from .scope import Scope, global_scope, scope_guard  # noqa: F401

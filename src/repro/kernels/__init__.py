# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
from typing import Optional


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Interpret mode for a Pallas call: as asked, else only on the CPU
    backend (tests), never on an accelerator."""
    if interpret is not None:
        return interpret
    import jax
    return jax.default_backend() == "cpu"

"""The system under test as the drivers build it, imported only when a run
needs it."""
from __future__ import annotations

from typing import Dict


def model(group: Dict):
    """The program's model at the widths and precisions of a
    configuration's ``model`` group."""
    from repro.configs import get_config
    from repro.models import build
    return build(get_config(group["arch"]).with_(
        d_model=group["embed_dim"], d_ff=group["hidden"],
        vocab=group["vocab"], param_dtype=group["param_dtype"],
        compute_dtype=group["compute_dtype"]))

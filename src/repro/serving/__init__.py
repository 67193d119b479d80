from .engine import ServeEngine, Request
from .continuous import (
    ContinuousEngine,
    copy_slot,
    open_migration,
    pack_slot,
    serve_reset_slot,
    slot_nbytes,
    unpack_slot,
)

__all__ = [
    "ServeEngine", "Request", "ContinuousEngine", "serve_reset_slot",
    "copy_slot", "pack_slot", "unpack_slot", "slot_nbytes", "open_migration",
]

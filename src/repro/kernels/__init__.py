from .common import resolve_interpret

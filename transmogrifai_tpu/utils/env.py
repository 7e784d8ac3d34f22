"""The one parse of a ``TMOG_*`` environment knob, below every layer that
reads one (stdlib only, so `ops/`, `parallel/`, `readers/` and `serve/`
import it without reaching upward)."""
from __future__ import annotations

import os


def env_on(name: str, default: str = "1") -> bool:
    """Tri-state toggle: on unless the value is one of 0 / false / off."""
    return os.environ.get(name, default).strip().lower() \
        not in ("0", "false", "off")


def _env_number(name: str, default, cast):
    try:
        return cast(os.environ[name].strip())
    except (KeyError, ValueError):
        return default


def env_int(name: str, default: int) -> int:
    """Integer knob; unset or unparsable reads as `default` (a typo in a
    tuning knob must not break a fit)."""
    return _env_number(name, default, int)


def env_float(name: str, default: float) -> float:
    """Float knob; unset or unparsable reads as `default`."""
    return _env_number(name, default, float)

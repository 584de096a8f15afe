"""The run's clock.  Every line a run says before its result carries
`since_start_s`, seconds since the process started, so that a run cut
at its time limit shows in which phase it was — and `run.py` imports
this module before anything heavy, so the clock starts with the
process (the set-up time `setup_s` is read off the same clock)."""

from __future__ import annotations

import json
import time

START = time.monotonic()


def since_start() -> float:
    return time.monotonic() - START


def say(**kw) -> None:
    print(json.dumps(dict(kw, since_start_s=since_start())), flush=True)

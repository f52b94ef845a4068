"""Bytes of recurrent state the cache holds on the device: the lanes' slots
and the snapshot pool (`stats()["ssm"]`: `state_bytes` + `snapshot_bytes`),
at the window's end."""

from __future__ import annotations


def read(run: dict):
    ssm = (run.get("stats1") or {}).get("ssm")
    if not ssm:
        return None
    return (ssm["state_bytes"] + ssm["snapshot_bytes"]) / 1e9

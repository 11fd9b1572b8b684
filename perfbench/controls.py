"""Broken variants of the timed path, to show that the check fails them.

A benchmark run never passes `--control`; these exist for the benchmark's own
tests and for control runs at a cell's size on the chip. Each patches
the system under test inside this process for the length of one run:

controls (the reference in the program's place, one stated guarantee
broken, the shortcut a later change might be tempted by):
- cas_padded_row (scan): CAS IDs of small files over the whole staged
  102,400-byte row, zero padding included, not the file alone (the
  fault a packing change to staging could make);
- checksum_sampled (verify): "full-file" checksums over the CAS sample
  only, so bit rot between the samples goes unseen;

faults (the timed path broken underneath):
- answer_altered: one CAS ID, or one checksum, changed where produced;
- half_batch: half of a batch's answers left out;
- state_unchanged: a job step that returns without doing its work.
"""

from __future__ import annotations

import struct
from contextlib import contextmanager
from typing import Callable, Dict, Tuple

from .reference import blake3_np

ROW = blake3_np.MINIMUM_FILE_SIZE


def _read(path: str) -> bytes:
    with open(path, "rb") as f:
        return f.read()


def _cas_padded_row(orig):
    def cas_ids(files, backend="auto"):
        ids = {}
        for start in range(0, len(files), 1024):
            msgs = []
            for path, size in files[start:start + 1024]:
                data = _read(path)
                msgs.append(blake3_np.cas_message(data) if size > ROW else
                            struct.pack("<Q", size) + data.ljust(ROW, b"\0"))
            for k, d in enumerate(blake3_np.blake3_many(msgs)):
                ids[start + k] = d.hex()[:16]
        return ids, {}
    return cas_ids


def _cas_altered(orig):
    def cas_ids(files, backend="auto"):
        ids, errors = orig(files, backend=backend)
        if ids:
            k = min(ids)
            ids[k] = "0" * 16 if ids[k] != "0" * 16 else "1" * 16
        return ids, errors
    return cas_ids


def _cas_half(orig):
    def cas_ids(files, backend="auto"):
        ids, errors = orig(files, backend=backend)
        return {i: v for i, v in ids.items() if i % 2 == 0}, errors
    return cas_ids


def _checksum_sampled(orig):
    def checksums(self, jobs, errors):
        datas = [_read(path) for _r, path in jobs]
        sums = blake3_np.checksums([blake3_np.cas_message(d) for d in datas])
        for (r, path), s in zip(jobs, sums):
            yield r, path, s
    return checksums


def _checksum_altered(orig):
    def checksums(self, jobs, errors):
        for k, (r, path, s) in enumerate(orig(self, jobs, errors)):
            yield r, path, (("0" * 64 if s != "0" * 64 else "1" * 64)
                            if k == 0 else s)
    return checksums


def _checksum_half(orig):
    def checksums(self, jobs, errors):
        for k, item in enumerate(orig(self, jobs, errors)):
            if k % 2 == 0:
                yield item
    return checksums


def _step_unchanged(orig):
    def step(self, ctx, data, *args):
        from spacedrive_tpu.jobs.job import StepOutcome

        if getattr(self, "mode", None) == "fill":
            return orig(self, ctx, data, *args)  # set-up's fill stays sound
        return StepOutcome()
    return step


def _targets(round_kind: str) -> Dict[str, Tuple[str, str, Callable]]:
    ident = "spacedrive_tpu.objects.identifier"
    val = "spacedrive_tpu.objects.validator"
    if round_kind == "scan":
        return {
            "cas_padded_row": (ident, "cas_ids_for_files", _cas_padded_row),
            "answer_altered": (ident, "cas_ids_for_files", _cas_altered),
            "half_batch": (ident, "cas_ids_for_files", _cas_half),
            "state_unchanged": (ident, "FileIdentifierJob._step",
                                _step_unchanged),
        }
    return {
        "checksum_sampled": (val, "ObjectValidatorJob._checksums_jax",
                             _checksum_sampled),
        "answer_altered": (val, "ObjectValidatorJob._checksums_jax",
                           _checksum_altered),
        "half_batch": (val, "ObjectValidatorJob._checksums_jax",
                       _checksum_half),
        "state_unchanged": (val, "ObjectValidatorJob._step",
                            _step_unchanged),
    }


@contextmanager
def applied(name: str, round_kind: str):
    """Patch the named control or fault in for the block."""
    import importlib

    targets = _targets(round_kind)
    if name not in targets:
        raise SystemExit(f"no control {name!r} for {round_kind} rounds; "
                         f"known: {sorted(targets)}")
    module, attr, make = targets[name]
    owner = importlib.import_module(module)
    *path, leaf = attr.split(".")
    for part in path:
        owner = getattr(owner, part)
    orig = getattr(owner, leaf)
    setattr(owner, leaf, make(orig))
    try:
        yield
    finally:
        setattr(owner, leaf, orig)

"""Phase0Spec: one object per preset bundling constants, types, and functions.

The reference builds its executable spec by compiling markdown into a module
and mutating module globals per preset (/root/reference scripts/build_spec.py,
Makefile:76-82). Here the same surface is a per-preset *object*: constants are
attributes, SSZ classes are attributes, and every spec function from
helpers/epoch/block/genesis is bound as a method. Two presets coexist as two
independent spec objects (the reference needs global mutation +
`init_SSZ_types` re-execution for that, build_spec.py:108-144).
"""
from __future__ import annotations

import inspect
from types import MethodType, ModuleType
from typing import Dict, Union

from ...crypto import bls
from ...utils.config import Preset, load_preset
from . import block as block_mod
from . import containers
from . import epoch as epoch_mod
from . import genesis as genesis_mod
from . import helpers as helpers_mod
from . import validator as validator_mod

_FUNCTION_MODULES = (helpers_mod, epoch_mod, block_mod, genesis_mod, validator_mod)


class Phase0Spec:
    """Executable phase-0 spec for a single constant preset."""

    def __init__(self, preset: Preset):
        self.config = preset
        self.name = preset.name

        # Constants (preset values + derived/initial values)
        for key, value in preset.items():
            setattr(self, key, value)
        self.GENESIS_EPOCH = self.GENESIS_SLOT // self.SLOTS_PER_EPOCH
        self.ZERO_HASH = b"\x00" * 32

        # Crypto boundary: the module, so the global bls_active switch and
        # backend selection apply to all spec objects at once.
        self.bls = bls

        # SSZ container types specialized to this preset's shapes (the dict
        # is kept so later phases extend THESE classes, not fresh rebuilds)
        self.container_types: Dict[str, type] = containers.build_types(self)
        for type_name, typ in self.container_types.items():
            setattr(self, type_name, typ)

        # Spec functions -> bound methods
        for mod in _FUNCTION_MODULES:
            self._bind_module(mod)

        # Phase-1 insert hooks (reference's `# @label` mechanism) and the
        # appended-operation-family hook consumed by process_operations
        self._insert_after_registry_updates = []
        self._insert_after_final_updates = []
        self._extra_block_operations = []   # (body_attr, max_count, handler)

        # Deferred-verification sink: when process_operations batches a
        # block's attestation signature checks, validate_indexed_attestation
        # appends (pubkey_sets, message_hashes, signature, domain) here
        # instead of verifying inline (block.process_attestations_batched)
        self._att_verify_sink = None

        # Streaming firehose hook (ISSUE 15): a streaming.StreamingVerifier
        # installed here serves the sink's verdicts from its cross-slot
        # queue/verdict cache instead of a per-block verify_indexed_batch
        # dispatch (block.process_attestations_batched)
        self._streaming_verifier = None

        # Registry views (helpers.registry_view): id(state) -> the view a
        # resident core registered for the state it holds as columns; every
        # other state is answered by its own validator list
        self._registry_views: Dict[int, object] = {}
        # helpers.ObjectRegistry's memo of the last object registry a
        # deposit looked a pubkey up in
        self._pubkey_index: list = [None, {}, 0]

        # Caches (reference epilogue: build_spec.py:78-105)
        self._hash_cache: Dict[bytes, bytes] = {}
        self._perm_cache: Dict = {}

    def _bind_module(self, mod: ModuleType) -> None:
        for fn_name, fn in vars(mod).items():
            if fn_name.startswith("_") or not inspect.isfunction(fn):
                continue
            if getattr(fn, "__module__", None) != mod.__name__:
                continue  # skip imports like np helpers
            params = list(inspect.signature(fn).parameters)
            if params and params[0] == "spec":
                setattr(self, fn_name, MethodType(fn, self))

    def clear_caches(self) -> None:
        self._hash_cache.clear()
        self._perm_cache.clear()
        self._pubkey_index[:] = [None, {}, 0]

    def __repr__(self):
        return f"Phase0Spec(preset={self.name!r})"


_spec_cache: Dict[str, Phase0Spec] = {}


def get_spec(preset: Union[str, Preset] = "minimal") -> Phase0Spec:
    """Build (and cache) the phase-0 spec for a preset name or Preset object."""
    if isinstance(preset, Preset):
        return Phase0Spec(preset)
    if preset not in _spec_cache:
        _spec_cache[preset] = Phase0Spec(load_preset(preset))
    return _spec_cache[preset]

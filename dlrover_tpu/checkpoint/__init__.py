"""Flash checkpoint: async sharded save/restore with reshard-on-restore,
plus the peer-to-peer restore path (surviving hosts donate state).

The names below resolve on first use (PEP 562), so that importing one
submodule, as the agent imports ``peer_restore`` for its donor, loads
what that submodule needs and not Orbax and JAX besides."""

import importlib

_SUBMODULE_OF = {
    "FlashCheckpointer": "flash_checkpoint",
    "abstract_state_for": "flash_checkpoint",
    "PeerDonorServer": "peer_restore",
    "PeerRestorer": "peer_restore",
    "PeerStateStore": "peer_restore",
    "abstract_encoded": "quantized",
    "decode_tree": "quantized",
    "encode_tree": "quantized",
    "encoded_nbytes": "quantized",
}

__all__ = sorted(_SUBMODULE_OF)


def __getattr__(name):
    submodule = _SUBMODULE_OF.get(name)
    if submodule is None:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}")
    value = getattr(importlib.import_module(f"{__name__}.{submodule}"),
                    name)
    globals()[name] = value
    return value

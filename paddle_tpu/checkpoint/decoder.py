"""Decoder checkpoints: persist/restore the ``DecoderSpec`` /
``decoder_step`` parameter-tree contract (ISSUE 12).

``save_decoder_checkpoint`` writes the spec into the manifest's meta
and the parameter tree into the payload; ``load_decoder_checkpoint``
restores both and VALIDATES the tensor set against the spec before
anything touches a device — a missing, extra, or wrong-shape tensor is
a typed error naming the tensor, never a shape error three layers into
``decoder_step``. Round-trips are bitwise: a loaded decoder serves
exactly the tokens the saving engine served (tier-1 pins greedy
equality through a fresh server)."""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import numpy as np

from .format import (CheckpointError, load_checkpoint_tree,
                     save_checkpoint_tree)

__all__ = ["save_decoder_checkpoint", "load_decoder_checkpoint",
           "expected_decoder_tensors", "decoder_checkpoint_mesh"]


def expected_decoder_tensors(spec) -> Dict[str, Tuple[int, ...]]:
    """Flat ``{name: shape}`` the decoder param-tree contract implies
    for ``spec`` — asked of the model (``models/decoders.py``), which
    works it out from the spec alone (no parameter draws), so
    validation is cheap even for models whose seed-build would not be.
    The names mirror the model's tree under the ``format._flatten``
    scheme (tuples index as ``/0``, ``/1``)."""
    return spec.tensors()


def save_decoder_checkpoint(dirname: str, spec,
                            params: Optional[Dict[str, Any]] = None,
                            step: Optional[int] = None,
                            base_manifest: Optional[str] = None,
                            mesh_axes: Optional[Any] = None,
                            mesh_rules: Optional[Any] = None,
                            shard_axis: Optional[str] = None) -> str:
    """Persist a decoder (spec + parameter tree) as a manifest
    checkpoint. ``params=None`` saves the spec's deterministic
    seed-built tree (the test/bench vehicle); a live engine passes its
    own tree. ``step`` (optional) rides the meta so
    ``fluid.io.latest_checkpoint_step`` recognizes the directory.
    ``base_manifest`` (ISSUE 13, the rollout loop's incremental save)
    names a prior decoder checkpoint DIRECTORY: only tensors whose
    crc32 differs from the base are written — the rest become base
    references the loader follows — so a fine-tune that touched two
    layers costs two layers of payload, not the whole model.

    ``mesh_axes`` (ISSUE 15) RECORDS the serving mesh in the manifest
    meta — ``load_decoder(checkpoint_dir=)`` then deploys the engine
    sharded exactly as exported, no operator knob needed;
    ``mesh_rules`` overrides the default ``mesh.decoder_rules``.
    ``shard_axis`` additionally writes the SHARDED payload layout (one
    file per shard of that mesh axis, merged manifest) instead of one
    monolithic payload; it requires ``mesh_axes`` and is incompatible
    with ``base_manifest`` (delta chains are a monolithic-layout
    feature)."""
    import numpy as _np

    if params is None:
        import jax

        params = jax.device_put(spec.seeded_arrays())
    meta: Dict[str, Any] = {"kind": "decoder", "spec": spec.to_dict()}
    if step is not None:
        meta["step"] = int(step)
    if shard_axis is not None and mesh_axes is None:
        raise CheckpointError(
            "shard_axis needs mesh_axes — the shard count is that mesh "
            "axis's size")
    if mesh_axes is not None:
        from ..mesh import MeshSpec, ShardingRules, decoder_rules

        ms = MeshSpec.coerce(mesh_axes)
        rules = ShardingRules.coerce(mesh_rules, default=decoder_rules)
        if shard_axis is not None:
            if base_manifest is not None:
                raise CheckpointError(
                    "sharded decoder checkpoints do not support "
                    "base_manifest deltas — save monolithic or full")
            import jax

            from .sharded import save_sharded_checkpoint

            # jax arrays (possibly device-sharded) -> host before the
            # splitter slices them
            host = jax.tree_util.tree_map(_np.asarray, params)
            return save_sharded_checkpoint(
                dirname, host, shard_axis=str(shard_axis),
                mesh_spec=ms, rules=rules, meta=meta)
        meta["mesh"] = {"spec": ms.to_dict(), "rules": rules.to_dict()}
    return save_checkpoint_tree(dirname, params, meta=meta,
                                base=base_manifest)


def decoder_checkpoint_mesh(dirname: str) -> Optional[Dict[str, Any]]:
    """The mesh a decoder checkpoint RECORDED at export (``{"spec":
    MeshSpec dict, "rules": ShardingRules dict}``), or None for
    single-chip artifacts. Reads only the manifest — no payload I/O —
    so the serving deploy path can decide the engine's mesh before
    loading a single tensor."""
    from .format import read_manifest

    manifest = read_manifest(dirname)
    meta = manifest.get("meta") or {}
    return meta.get("mesh")


def load_decoder_checkpoint(dirname: str, verify: bool = True):
    """Restore ``(DecoderSpec, params)`` from a decoder checkpoint.
    The params come back as HOST arrays (read-only views of the
    mapped payload) ready for ``DecodeEngine(..., params=)``, which
    places each one on the device or mesh shard that serves it; the
    tensor set is validated against the spec FIRST (names and shapes),
    so a wrong-model or hand-edited checkpoint fails with the offending
    tensor named."""
    from ..models.decoders import spec_from_dict

    tree, manifest = load_checkpoint_tree(dirname, verify=verify)
    meta = manifest.get("meta") or {}
    if meta.get("kind") != "decoder":
        raise CheckpointError(
            f"'{dirname}' is a {meta.get('kind') or 'generic'} "
            "checkpoint, not a decoder checkpoint (no DecoderSpec in "
            "its meta)")
    spec = spec_from_dict(dict(meta["spec"]))

    # validate the FLAT view against the analytic contract before any
    # device transfer
    from .format import _flatten

    flat, _skel = _flatten(tree)
    want = expected_decoder_tensors(spec)
    import jax.numpy as jnp

    want_dtype = np.dtype(jnp.dtype(spec.param_dtype))
    missing = sorted(set(want) - set(flat))
    extra = sorted(set(flat) - set(want))
    if missing or extra:
        raise CheckpointError(
            f"decoder checkpoint '{dirname}' does not match its spec's "
            f"parameter contract: missing {missing or 'none'}, "
            f"unexpected {extra or 'none'}")
    for name, shape in want.items():
        got = tuple(flat[name].shape)
        if got != shape:
            raise CheckpointError(
                f"tensor '{name}' in '{dirname}' has shape {got}, "
                f"spec requires {shape}")
        dt = np.dtype(flat[name].dtype)
        if dt != want_dtype:
            # refuse, don't cast: jnp.asarray would silently squeeze a
            # float64 (or quantized) tree into the served dtype and the
            # served tokens would differ from the saved model's — the
            # bitwise-roundtrip promise dies without a named error
            raise CheckpointError(
                f"tensor '{name}' in '{dirname}' is {dt}, the "
                f"{spec.family} decoder contract serves {want_dtype} — "
                "convert at save time, never implicitly at deploy")

    return spec, tree

"""Shard-local quantized exchange (§Perf optimization, beyond the paper).

The faithful baseline quantizes each parameter leaf GLOBALLY: the Hadamard
rotation reshapes the flattened leaf into 16k blocks that straddle shard
boundaries, so GSPMD inserts all-gathers before/after every rotation — the
dominant collective cost of the train step for the FSDP (cohort) archs.

Blockwise rotation is valid for ANY partition into blocks, so we instead run
the entire exchange inside one ``shard_map``: every device rotates/encodes/
decodes only its LOCAL chunk of every leaf (rotation key folded with the
model-axis index so codes stay decodable across the client axis), and the
only collectives left are the ones the ALGORITHM requires:

  * hint psums (scalar per leaf),
  * the client-sum for the server update, carried by a pluggable
    :class:`repro.compression.transports.Transport` strategy — fp32 psum
    (``shard_local``), an all-gather of the packed codec codes
    (``code_allgather``; with ``lattice_packed`` the gathered bytes shrink
    by the packing factor), or the fused ``reduce_scatter`` path that
    psum-scatters the SNAPPED rotated chunks and re-gathers them as a
    scatter-resident COMPRESSED downlink: each device lattice-encodes its
    own reduced shard at the downlink wire width and the all-gather moves
    packed integer codes + the γ-shards row instead of fp32 (the exchange
    derives the shared redistribution scale γ_rs from psum'd hints here,
    where the model axes are known, and hands it to the transport).

Semantics are an exact instance of Alg. 1 with a different (shard-aligned)
rotation block partition; ``shard_local`` and ``code_allgather`` compute
the same aggregate exactly, the fused ``reduce_scatter`` up to its
redistribution quantization (bounded like any downlink encode).

Compression is codec-composable: ``quant_up`` / ``quant_down`` are
:mod:`repro.compression.codecs` objects resolved per direction. A
lattice-family pair runs the ROTATED-SPACE path through the compression
pipeline — 3 forward passes per chunk (the fused rotate+encode of the
client update Y, the server rotation that serves as the uplink decode
reference, and the server's fused downlink encode, whose γ depends on the
decoded uplink), every snap/sum on rotated coordinates via the fused
kernels, only the two new states inverse-rotated (2 passes); the per-
direction wire descriptors thread bit-widths and sub-byte packing into the
kernels. Any other codec pair runs the per-message composition with the
same collective structure. The downlink Enc(X_t) is decoded against the
client's CURRENT model Y^i — the same reference rule as the flat
simulator's pipeline.quafl_round. Rounding noise is folded with the client
index; rotation keys remain shared across clients so codes stay
cross-decodable.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compression.codecs import is_lattice_family
from repro.compression.pipeline import ExchangePipeline, LatticeWire
from repro.compression.rotation import dither
from repro.utils.compat import shard_map
from repro.utils.tree import fold_in_str


def _pad1024(x):
    d = x.shape[0]
    pad = (-d) % 1024
    return (jnp.pad(x, (0, pad)) if pad else x), d


def rs_gamma(pipe: ExchangePipeline, wire_dn: LatticeWire, h_sum, nrm_sum,
             d: int):
    """Redistribution scale of the scatter-resident coded downlink.

    ``h_sum`` is the psum over clients of the per-client snap distances
    ‖QYᵢ − rot(X_t)‖: by the triangle inequality it upper-bounds
    ‖Σᵢ QYᵢ − n·rot(X_t)‖, so the aggregate satisfies the Lemma 3.1 wrap
    condition at this γ. Factored out so the γ-overflow interval analysis
    (``repro.analysis.intervals``) proves the wrap window on the SAME
    traced derivation the exchange runs.
    """
    wire_rs = LatticeWire(bits=wire_dn.bits, pack=wire_dn.pack)
    return pipe.gammas(h_sum[None], nrm_sum[None], d, wire_rs), wire_rs


def make_shardlocal_exchange(quant_up, quant_down, mesh,
                             srv_pspecs: Dict[str, P],
                             cl_pspecs: Dict[str, P], client_axis: str,
                             n_slots: int, transport):
    """Returns exchange(server, clients, Ys, key) -> (server_new,
    clients_new, qerr) with all quantization math device-local.

    ``quant_up`` / ``quant_down`` are per-direction codecs;
    ``transport`` a :class:`repro.compression.transports.Transport`
    carrying the uplink client-sum collective.
    """
    mesh_axes = list(mesh.shape.keys())
    model_axes = tuple(a for a in mesh_axes if a != client_axis)
    client_in_mesh = client_axis in mesh.shape
    denom = n_slots + 1
    lattice_pair = (is_lattice_family(quant_up)
                    and is_lattice_family(quant_down))
    pipe = (ExchangePipeline(bits=quant_up.bits, block=quant_up.block,
                             safety=quant_up.safety,
                             backend=quant_up.backend)
            if lattice_pair else None)
    wire_up = quant_up.wire() if lattice_pair else None
    wire_dn = quant_down.wire() if lattice_pair else None

    def _psum_norm(sq, axes):
        for a in axes:
            sq = jax.lax.psum(sq, a)
        return jnp.sqrt(sq)

    def _lattice_leaf(kk, srv, y, cl_flat):
        """Rotated-space exchange of one local leaf chunk: 3 forward + 2
        inverse rotation passes with the chunk-shared key (cl_flat only
        feeds the uplink hint; the downlink decodes against y)."""
        d = srv.shape[0]
        kk_cl = (jax.lax.axis_index(client_axis) if client_in_mesh else 0)
        k_up, k_dn = jax.random.fold_in(kk, 1), jax.random.fold_in(kk, 2)
        signs = pipe.signs_for(jax.random.split(k_up)[0], d)
        d_pad = signs.shape[0]

        # hints: ||Y - X^i|| over the model axes (client-local value)
        h_up = _psum_norm(jnp.sum(jnp.square(y - cl_flat)),
                          model_axes) + 1e-8
        gam_up = pipe.gammas(h_up[None], jnp.linalg.norm(y)[None], d,
                             wire_up)
        u_up = dither(jax.random.fold_in(jax.random.split(k_up)[1], kk_cl),
                      (1, d_pad))
        y_rot, codes = pipe.rotate_encode(y[None], signs, u_up, gam_up,
                                          wire=wire_up)
        srv_rot = pipe.rotate(srv[None], signs)
        qy_own = pipe.snap(codes, srv_rot, gam_up, wire_up)      # rotated
        # per-client distance to the decode reference (feeds the downlink
        # hint and, summed over clients, the coded-redistribution scale)
        h_cl = _psum_norm(jnp.sum(jnp.square(qy_own - srv_rot)), model_axes)
        # client-sum strategy: the pluggable transport decides which bytes
        # cross the interconnect (fp32 partials, packed codes, or the
        # scatter-resident coded shards of the fused reduce_scatter path)
        fused_rs = getattr(transport, "lattice_fused_sum", None)
        if fused_rs is not None and client_in_mesh:
            # ‖Σ QYᵢ − n·rot(X_t)‖ ≤ Σᵢ‖QYᵢ − rot(X_t)‖: the psum of the
            # per-client hints satisfies the wrap bound for the aggregate
            h_rs = jax.lax.psum(h_cl, client_axis) + 1e-8
            nrm_rs = jax.lax.psum(
                _psum_norm(jnp.sum(jnp.square(qy_own)), model_axes),
                client_axis)
            gam_rs, wire_rs = rs_gamma(pipe, wire_dn, h_rs, nrm_rs, d)
            k_rs = jax.random.fold_in(jax.random.split(k_dn)[0], kk_cl)
            qy_sum = fused_rs(pipe, wire_rs, qy_own, srv_rot, gam_rs,
                              k_rs, client_axis)
        else:
            qy_sum = transport.lattice_sum(pipe, wire_up, codes, gam_up,
                                           srv_rot, qy_own, client_axis,
                                           client_in_mesh,
                                           quant_up.code_dtype())
        srv_new_rot = (srv_rot + qy_sum) / denom

        # server -> client: encode once (same on every client slice),
        # decode against the client's current model Y — all in rotated
        # space, same reference rule as pipeline.quafl_round
        h_dn = h_cl
        if client_in_mesh:
            h_dn = jax.lax.pmax(h_dn, client_axis)
        gam_dn = pipe.gammas(2.0 * h_dn[None] + 1e-8,
                             jnp.linalg.norm(srv)[None], d, wire_dn)
        u_dn = dither(jax.random.split(k_dn)[1], (1, d_pad))
        codes_dn = pipe.rotate_encode(srv[None], signs, u_dn, gam_dn,
                                      want_rotated=False, wire=wire_dn)
        qx_rot = pipe.snap(codes_dn, y_rot, gam_dn, wire_dn)
        cl_new_rot = qx_rot / denom + n_slots * y_rot / denom

        srv_new = pipe.unrotate(srv_new_rot, signs, d)[0]
        cl_new = pipe.unrotate(cl_new_rot, signs, d)[0]
        qerr = jnp.sum(jnp.square(qy_own[0] - y_rot[0])) / n_slots
        return srv_new, cl_new, qerr

    def _generic_leaf(kk, srv, y, cl_flat):
        """Per-message composition for codec pairs without a shared
        rotation structure (scalar / identity / top-k / mixed)."""
        h_up = _psum_norm(jnp.sum(jnp.square(y - cl_flat)),
                          model_axes) + 1e-8
        k_up = jax.random.fold_in(kk, 1)
        msg = quant_up.encode(k_up, y, h_up)
        qy_own = quant_up.decode(k_up, msg, srv)
        qy_sum = transport.generic_sum(quant_up, k_up, msg, srv, qy_own,
                                       client_axis, client_in_mesh,
                                       n_slots)
        srv_new = (srv + qy_sum) / denom

        h_dn = _psum_norm(jnp.sum(jnp.square(qy_own - srv)), model_axes)
        if client_in_mesh:
            h_dn = jax.lax.pmax(h_dn, client_axis)
        k_dn = jax.random.fold_in(kk, 2)
        msg_s = quant_down.encode(k_dn, srv, 2.0 * h_dn + 1e-8)
        qx = quant_down.decode(k_dn, msg_s, cl_flat)
        cl_new = qx / denom + n_slots * y / denom
        qerr = jnp.sum(jnp.square(qy_own - y)) / n_slots
        return srv_new, cl_new, qerr

    leaf_fn = _lattice_leaf if pipe is not None else _generic_leaf

    def local_fn(server_l, clients_l, Ys_l, key):
        key = jax.random.wrap_key_data(key)
        # identity along the NON-client axes selects the rotation block; it
        # must be shared along the client axis so codes stay decodable.
        mid = 0
        for a in model_axes:
            mid = mid * mesh.shape[a] + jax.lax.axis_index(a)
        qerr = jnp.zeros((), jnp.float32)
        server_new, clients_new = {}, {}
        for k in server_l:
            kk = jax.random.fold_in(fold_in_str(key, k), mid)
            srv, _ = _pad1024(server_l[k].astype(jnp.float32).ravel())
            cl = clients_l[k][0]
            y, dlen = _pad1024(Ys_l[k][0].astype(jnp.float32).ravel())
            cl_flat, _ = _pad1024(cl.astype(jnp.float32).ravel())

            srv_new, cl_new, qerr_k = leaf_fn(kk, srv, y, cl_flat)
            qerr += qerr_k
            shp, dt = server_l[k].shape, server_l[k].dtype
            server_new[k] = srv_new[:dlen].reshape(shp).astype(dt)
            clients_new[k] = cl_new[:dlen].reshape((1,) + shp).astype(
                clients_l[k].dtype)
        for a in model_axes:
            qerr = jax.lax.psum(qerr, a)
        # qerr varies per client slot (each device quantizes its own Y^i);
        # committing it replicated (out_spec P()) without reducing over the
        # client axis would publish client 0's value — the divergence class
        # repro.analysis.divergence flags. Reduce to the sum over clients.
        if client_in_mesh:
            qerr = jax.lax.psum(qerr, client_axis)
        return server_new, clients_new, qerr

    in_specs = (srv_pspecs, cl_pspecs, cl_pspecs, P())
    out_specs = (srv_pspecs, cl_pspecs, P())
    fn = shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs, check_vma=False)

    def exchange(server, clients, Ys, key_data):
        return fn(server, clients, Ys, key_data)

    return exchange

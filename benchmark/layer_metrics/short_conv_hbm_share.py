"""The gated short convolution, its share of the chip's HBM peak: the bytes
the OPERATION needs, whatever implements it
(``benchmark/sconv_decoder_flops.py`` ``short_conv_bytes``: ``[B | C | x]``
read and ``y`` written forward; those and ``dy`` read and ``d[B | C | x]``
written backward; forward once more where the block is recomputed; in the
compute dtype), over the device time a step of the steady trace spends under
the ``tm.lm.sconv`` scope (forward, recomputation and backward,
``benchmark/model_scopes.py``) and the HBM peak
(``benchmark/sparse_attention_roofline.py``). The operation computes almost
nothing (8 operations an element), so bytes bound it. XLA's expressions pad
a float32 copy and read it at three row offsets, forward and backward, so
the share reads low: that is the reading the operation's kernel moves. It
cannot pass 100 % by moving more bytes. None where the program has no such
scope or the configuration no such layer."""

from benchmark import model_scopes


def read(run):
    cfg = run["cfg"]
    layers = cfg.get("layer_types", ()).count("conv")
    ms = model_scopes.bucket_ms_per_step(run, "tm.lm.sconv")
    if not layers or not ms:
        return None
    import jax
    import jax.numpy as jnp

    from benchmark import sconv_decoder_flops as count
    from benchmark import sparse_attention_roofline as roofline

    needed = layers * cfg["per_chip_batch"] * count.short_conv_bytes(
        cfg["sequence_length"], cfg["hidden_size"],
        jnp.dtype(cfg["compute_dtype"]).itemsize, cfg["remat"])
    peak = roofline.peak_hbm_bytes_per_s(jax.devices()[0].device_kind)
    return 100.0 * needed / (1e-3 * ms * peak)

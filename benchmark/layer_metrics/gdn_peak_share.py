"""The gated delta rule, its share of the chip's peak: the operations the
RECURRENCE needs for a step's rule, forward and backward
(``benchmark/deltanet_decoder_flops.py`` ``delta_rule_forward_flops``, three
forward passes' worth: a state's decay, read, update and output a position
and value head, whatever the chunking or a kernel does), over the device
time a step of the steady trace spends under the ``tm.lm.gdn_chunk`` and
``tm.lm.gdn_state`` scopes (forward, recomputation and backward,
``benchmark/model_scopes.py``) and the chip's bf16 peak
(``benchmark/flops.py``). The program solves a triangular system a chunk in
float32 products of ``[64, 64]``, multiplies a chunk's pairs besides,
crosses HBM between its steps and recomputes the layer in backward, so the
share reads low: that is the reading a kernel moves. It cannot pass 100 % by
doing more work. None where the program has no such scopes or the
configuration no such layers (its file states the chunk under
``model.gdn_chunk``)."""

from benchmark import model_scopes


def read(run):
    cfg = run["cfg"]
    if "gdn_chunk" not in cfg.get("model", {}):
        return None
    ms = model_scopes.bucket_ms_per_step(
        run, "tm.lm.gdn_chunk", "tm.lm.gdn_state")
    if not ms:
        return None
    import jax

    from benchmark import deltanet_decoder_flops as count, flops

    layers, interval = cfg["num_hidden_layers"], cfg["full_attention_interval"]
    needed = (layers - layers // interval) * cfg["per_chip_batch"] * (
        flops.train_flops(count.delta_rule_forward_flops(
            cfg["sequence_length"], cfg["linear_num_value_heads"],
            cfg["linear_key_head_dim"], cfg["linear_value_head_dim"])))
    peak = flops.peak_flops(jax.devices()[0].device_kind)
    return 100.0 * needed / (1e-3 * ms * peak)

"""Power retention, its share of the chip's peak: the operations the
RECURRENCE needs for a step's retention, forward and backward
(``benchmark/retention_decoder_flops.py`` ``retention_forward_flops``, three
forward passes' worth: the symmetric state's decay, update and read and the
symmetric squares, whatever the chunking or a kernel does), over the device
time a step of the steady trace spends under the ``tm.lm.ret_state`` and
``tm.lm.ret_chunk`` scopes (forward, recomputation and backward,
``benchmark/model_scopes.py``) and the chip's bf16 peak
(``benchmark/flops.py``). The program makes the symmetric squares in XLA
operations that cross HBM, recomputes the layer in backward and multiplies
a chunk's pairs besides, so the share reads low: that is the reading a
kernel moves. It cannot pass 100 % by doing more work. None where the
program has no such scopes or the configuration no retention (its file
states the chunk under ``model.retention_chunk``)."""

from benchmark import model_scopes


def read(run):
    cfg = run["cfg"]
    if "retention_chunk" not in cfg.get("model", {}):
        return None
    ms = model_scopes.bucket_ms_per_step(
        run, "tm.lm.ret_state", "tm.lm.ret_chunk")
    if not ms:
        return None
    import jax

    from benchmark import flops, retention_decoder_flops as count

    needed = cfg["num_hidden_layers"] * cfg["per_chip_batch"] * (
        flops.train_flops(count.retention_forward_flops(
            cfg["sequence_length"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"])))
    peak = flops.peak_flops(jax.devices()[0].device_kind)
    return 100.0 * needed / (1e-3 * ms * peak)

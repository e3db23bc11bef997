"""Multi-host runtime pieces (core/distributed.py) — testable single-host by
mocking process topology; the real cross-host path is exercised by the same
code because jax.make_array_from_process_local_data degenerates to
device_put semantics at process_count == 1."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.core.distributed import (
    place_host_local_batch,
    process_batch_slice,
)
from megatron_llm_tpu.data.samplers import (
    MegatronPretrainingSampler,
    _ProcessSlicedSampler,
    build_pretraining_data_loader,
)


def test_process_batch_slice_partitions_the_batch():
    with mock.patch.object(jax, "process_count", return_value=4):
        slices = []
        for pid in range(4):
            with mock.patch.object(jax, "process_index", return_value=pid):
                slices.append(process_batch_slice(16))
    assert slices == [(0, 4), (4, 8), (8, 12), (12, 16)]
    # rows cover the batch exactly once, in order (matches the contiguous
    # row-major (dp, ep) batch sharding)
    covered = [i for a, b in slices for i in range(a, b)]
    assert covered == list(range(16))


def test_process_batch_slice_requires_divisibility():
    with mock.patch.object(jax, "process_count", return_value=3):
        with pytest.raises(AssertionError):
            process_batch_slice(16)


def test_process_sliced_sampler_keeps_global_bookkeeping():
    base = MegatronPretrainingSampler(
        total_samples=32, consumed_samples=8, global_batch_size=8
    )
    sliced = _ProcessSlicedSampler(base, 2, 4)  # host 1 of 4, 2 rows each
    batches = list(iter(sliced))
    # same number of global batches, each reduced to this host's rows
    assert len(batches) == 3
    assert batches[0] == [10, 11]  # rows 2:4 of global batch [8..16)
    assert batches[1] == [18, 19]
    assert batches[2] == [26, 27]


def test_loader_process_sliced_single_process_is_identity():
    class DS:
        def __len__(self):
            return 16

        def __getitem__(self, i):
            return {"x": np.full((4,), i, np.int32)}

    it = build_pretraining_data_loader(
        DS(), 0, 8, "single", process_sliced=True
    )
    batch = next(iter(it))
    assert batch["x"].shape == (8, 4)
    np.testing.assert_array_equal(batch["x"][:, 0], np.arange(8))


def test_place_host_local_batch_single_process_matches_device_put():
    from jax.sharding import NamedSharding, PartitionSpec as P

    from megatron_llm_tpu.core.parallel_state import build_mesh

    mesh = build_mesh(devices=jax.devices()[:4])
    sh = {"tokens": NamedSharding(mesh, P(("dp", "ep"), None)),
          "token_idx": NamedSharding(mesh, P(None))}
    batch = {"tokens": np.arange(32).reshape(4, 8),
             "token_idx": np.arange(8)}
    placed = place_host_local_batch(batch, sh)
    np.testing.assert_array_equal(np.asarray(placed["tokens"]),
                                  batch["tokens"])
    assert placed["tokens"].sharding.spec == P(("dp", "ep"), None)
    np.testing.assert_array_equal(np.asarray(placed["token_idx"]),
                                  batch["token_idx"])


def test_initialize_distributed_single_host_noop():
    from megatron_llm_tpu.core import distributed

    distributed._INITIALIZED = False
    distributed.initialize_distributed()  # must not raise or hang
    assert distributed._INITIALIZED


_WORKER = r"""
import os, sys
pid = int(sys.argv[1]); tmp = sys.argv[2]; port = sys.argv[3]
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=4"
os.environ["MEGATRON_COORDINATOR"] = "127.0.0.1:" + port
os.environ["MEGATRON_NUM_PROCESSES"] = "2"
os.environ["MEGATRON_PROCESS_ID"] = str(pid)

import numpy as np
from megatron_llm_tpu.core.distributed import initialize_distributed
initialize_distributed()
import jax
assert jax.process_count() == 2
assert len(jax.devices()) == 8

from megatron_llm_tpu.config import Config, apply_architecture
from megatron_llm_tpu.data.indexed_dataset import make_builder
from megatron_llm_tpu.training import pretrain
import time

prefix = os.path.join(tmp, "corpus_text_document")
ready = os.path.join(tmp, "data_ready")
if pid == 0:
    rng = np.random.RandomState(0)
    b = make_builder(prefix + ".bin", vocab_size=250)
    for _ in range(60):
        b.add_doc(rng.randint(1, 250, size=rng.randint(30, 80)))
    b.finalize(prefix + ".idx")
    open(ready, "w").write("1")
else:
    while not os.path.exists(ready):
        time.sleep(0.2)

cfg = Config()
apply_architecture(cfg, "llama2")
cfg.model.num_layers = 2; cfg.model.hidden_size = 64
cfg.model.num_attention_heads = 4; cfg.model.num_attention_heads_kv = 2
cfg.model.vocab_size = 256; cfg.model.max_position_embeddings = 64
cfg.data.seq_length = 32; cfg.data.data_path = [prefix]
cfg.data.tokenizer_type = "NullTokenizer"
cfg.training.params_dtype = "float32"; cfg.training.use_flash_attn = False
cfg.training.micro_batch_size = 2; cfg.training.global_batch_size = 8
cfg.training.train_iters = 4; cfg.training.eval_iters = 1
cfg.training.eval_interval = 2; cfg.logging.log_interval = 2
cfg.parallel.tensor_model_parallel_size = 2
cfg.checkpoint.save = os.path.join(tmp, "ckpt"); cfg.checkpoint.save_interval = 4
cfg.finalize(n_devices=8)

result = pretrain(cfg)
loss = float(result["last_metrics"]["lm loss"])
assert result["iteration"] == 4 and np.isfinite(loss)
print("WORKER_OK", pid, loss, flush=True)
"""


def test_two_process_pretrain_end_to_end(tmp_path):
    """REAL multi-process training: two OS processes, 4 virtual CPU devices
    each, jax.distributed over a localhost coordinator (gloo collectives),
    process-sliced data loading, dp x tp mesh spanning both processes,
    eval, and a multi-process orbax checkpoint save. Both processes must
    finish with the SAME loss (lockstep SPMD)."""
    import os
    import socket
    import subprocess
    import sys

    with socket.socket() as s:  # free port for the coordinator
        s.bind(("127.0.0.1", 0))
        port = str(s.getsockname()[1])

    worker = tmp_path / "worker.py"
    worker.write_text(_WORKER)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env["PYTHONPATH"] = "/root/repo" + os.pathsep + env.get("PYTHONPATH", "")
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), str(pid), str(tmp_path), port],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        for pid in (0, 1)
    ]
    outs = [p.communicate(timeout=420)[0] for p in procs]
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{out[-3000:]}"
    losses = [
        line.split()[2]
        for out in outs for line in out.splitlines()
        if line.startswith("WORKER_OK")
    ]
    assert len(losses) == 2 and losses[0] == losses[1], losses
    assert (tmp_path / "ckpt" / "iter_0000004").is_dir()

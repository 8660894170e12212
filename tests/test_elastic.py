"""Elastic recovery end-to-end on one host: worker loss -> stop signal ->
re-plan for survivors -> rebuild trainer under the new strategy -> resume
from checkpoint (reference: SURVEY §5.3 flow; BASELINE config 5
'survives worker loss')."""
import threading
import time

import jax
import numpy as np
import pytest

from hetu_tpu.core.mesh import MeshConfig
from hetu_tpu.data import pad_batch
from hetu_tpu.engine import ElasticController, Trainer, TrainingConfig
from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel
from hetu_tpu.parallel import ParallelStrategy
from hetu_tpu.rpc import CoordinationClient, CoordinationServer

# the first save of a process loads orbax, for seconds: before a heartbeat
# is timed, not while (conftest.orbax_loaded)
pytestmark = pytest.mark.usefixtures("orbax_loaded")


@pytest.mark.slow
def test_elastic_survives_worker_loss(tmp_path):
    server = CoordinationServer(world_size=2, heartbeat_timeout=1.0)
    me = CoordinationClient("127.0.0.1", server.port, heartbeat_interval=0.2)

    cfg = LlamaConfig.tiny(remat=False)
    rng = np.random.default_rng(0)
    batch = pad_batch([rng.integers(1, 250, size=60) for _ in range(8)], 64)
    strategies_used = []

    def planner(alive):
        # 2 workers -> dp4xtp2 plan; 1 survivor -> dp8 plan (ranks 0/1 both
        # call this; deterministic in `alive` so votes agree)
        from hetu_tpu.utils.parallel_config import generate_ds_parallel_config
        if len(alive) >= 2:
            return generate_ds_parallel_config(num_layers=2, dp=4, tp=2)
        return generate_ds_parallel_config(num_layers=2, dp=8)

    def factory(plan):
        from hetu_tpu.utils.parallel_config import read_ds_parallel_config
        st, _ = read_ds_parallel_config(plan)
        strategies_used.append(st.describe())
        tc = TrainingConfig(global_batch_size=8, micro_batch_size=1,
                            seq_len=64, lr=3e-3, warmup_steps=2,
                            total_steps=100, log_every=1000,
                            ckpt_dir=str(tmp_path / "ck"), ckpt_every=10 ** 9)
        model = LlamaLMHeadModel(cfg, st)
        return Trainer(model, tc, st).build()

    ctl = ElasticController(me, factory, planner)

    # the ghost runs its own (lightweight) controller — every worker
    # participates in plan votes — until it is killed
    class FakeTrainer:
        global_step = 0
        _ckpt = None

        def train_step(self, b):
            time.sleep(0.05)
            self.global_step += 1
            return {"loss": 0.0}

        def save(self, wait=False):
            pass

        def restore(self):
            raise FileNotFoundError

    ghost_hb = CoordinationClient("127.0.0.1", server.port,
                                  heartbeat_interval=0.2)
    ghost_ctl = ElasticController(ghost_hb, lambda plan: FakeTrainer(),
                                  planner)
    ghost_stop = threading.Event()

    def ghost_loop():
        ghost_ctl._rebuild()
        while not ghost_stop.is_set():
            time.sleep(0.1)

    ghost_thread = threading.Thread(target=ghost_loop, daemon=True)
    ghost_thread.start()

    def batches():
        """Event-driven kill: after 3 steps under the 2-worker plan, stop
        the ghost's heartbeats and BLOCK until the server's stop flag is
        visible on the survivor — the controller then deterministically
        re-plans before the next step (no sleep races under CPU load)."""
        for i in range(60):
            if i == 3:
                ghost_stop.set()
                ghost_hb._shutdown = True   # rank 1 stops heartbeating
                deadline = time.time() + 60.0
                while not (me.should_stop and me.check_stop()):
                    assert time.time() < deadline, \
                        "worker loss was never signaled"
                    time.sleep(0.05)
            yield batch

    trainer = ctl.run(batches(), num_steps=14)
    assert trainer.global_step >= 14
    # both strategies were used: pre-loss dp4xtp2, post-loss dp8
    assert any("tp2" in s for s in strategies_used)
    assert strategies_used[-1].startswith("dp8")
    # training progressed across the re-mesh (loss finite at the end)
    m = trainer.train_step(batch)
    assert np.isfinite(float(m["loss"]))
    me.exit()
    server.close()

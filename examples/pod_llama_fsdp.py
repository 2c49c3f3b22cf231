"""Pod-scale recipe: Llama-8B FSDP pretraining on a v5p-64 slice —
BASELINE.json config 5.

The reference tops out at DDP over NCCL (wrap at
/root/reference/dmlcloud/pipeline.py:72-74) and could not hold an 8B model
per GPU optimizer state anyway; this recipe is the committed shape of the
same training run done the TPU way: parameters, grads and Adam state
sharded over the mesh, XLA inserting the all-gathers/reduce-scatters.

## The v5p-64 recipe (16 hosts x 4 chips, 95 GB HBM each)

    srun python examples/pod_llama_fsdp.py \
        --preset 8b --mesh data=2,fsdp=32 \
        --global-batch 128 --seq-len 4096 \
        --checkpoint-dir gs://YOUR_BUCKET/runs/llama8b \
        --save-every-steps 250 --remat --chunked-loss 8192

Every choice, spelled out:

- **Mesh `data=2, fsdp=32`**: 8B params in fp32 master + Adam m/v is
  ~96 GB — more than one chip's HBM, so FSDP is mandatory, not optional.
  Over ``fsdp=32`` each chip holds ~3 GB of optimizer+param state, leaving
  room for activations at seq 4096. The ``data=2`` axis halves the
  all-gather volume per chip versus a flat ``fsdp=64`` (weights are
  gathered once per data replica) at the cost of 2x grad reduce-scatter —
  the right trade when per-step weight traffic dominates, which it does at
  this batch. Both axes carry the batch (parallel/mesh.py ``data_axes``).
- **Partition rules**: ``llama_partition_rules()`` (models/transformer.py:91)
  — every matmul kernel P('fsdp', 'model'); without a ``model`` axis this
  is pure FSDP. Add ``model=4`` at 70B+ scale where a single layer's
  kernels deserve splitting.
- **Per-host batch** = global/hosts = 128/16 = **8 sequences** of 4096
  tokens; global step = 128 x 4096 = 524k tokens. ``--grad-accum N``
  splits each global batch into N sequential microbatches inside the ONE
  jitted step (lax.scan — stage.py gradient_accumulation): the effective
  batch stays ``--global-batch`` while activation memory drops ~N×, so use
  it to fit a bigger global batch than activations would otherwise allow.
- **`--remat`**: block-granular rematerialisation; at 8B/s4096 activations
  without remat exceed HBM. It trades recomputed forward work for
  activation memory (neither is measured on the chip: no cell turns remat on).
- **`--chunked-loss 8192`**: the 128k-vocab logits tensor ([8, 4096,
  128256] bf16 = 8 GB per chip) is never materialised; chunked_lm_loss
  streams vocab blocks (models/transformer.py chunked_lm_loss).
- **Checkpoints**: Orbax to GCS, each host writing its own shards;
  ``--save-every-steps 250`` (~every 130M tokens) bounds preemption loss.
  Slurm requeue + ``--resume`` picks up bit-exact mid-epoch
  (tests/test_multiprocess.py mid-epoch resume).

## Toy run (any machine, e.g. the 8-device CPU mesh)

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    python examples/pod_llama_fsdp.py --toy --mesh data=2,fsdp=4

Same code path (mesh, rules, remat, chunked loss, step saves) on a tiny
decoder; only sizes differ.
"""

import argparse

import optax

import dmlcloud_tpu as dml
from dmlcloud_tpu.models.transformer import (
    DecoderLM,
    TransformerConfig,
    chunked_lm_loss,
    llama_partition_rules,
    lm_loss,
)
from dmlcloud_tpu.parallel import init_auto, parse_mesh_axes, runtime

PRESETS = {
    # Llama-3-8B geometry (models/hf.py imports real weights into this shape)
    "8b": dict(num_layers=32, num_heads=32, num_kv_heads=8, head_dim=128,
               hidden_dim=4096, mlp_dim=14336, vocab_size=128256),
    "toy": dict(num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                hidden_dim=64, mlp_dim=160, vocab_size=512),
}


class LlamaStage(dml.TrainValStage):
    def pre_stage(self):
        cfg = self.config
        model_cfg = TransformerConfig(
            max_seq_len=cfg.seq_len,
            attn_impl=cfg.attn,
            remat=bool(cfg.remat),
            # the flash kernel shard_maps itself over the mesh (XLA cannot
            # partition it)
            mesh=self.mesh if cfg.attn == "flash" else None,
            **PRESETS[cfg.preset],
        )
        self.model = DecoderLM(model_cfg)
        import jax.numpy as jnp

        self.pipeline.register_model(
            "llama",
            self.model,
            sharding=llama_partition_rules(),
            init_args=(jnp.zeros((1, 8), jnp.int32),),
        )
        schedule = optax.warmup_cosine_decay_schedule(
            0.0, cfg.lr, warmup_steps=cfg.warmup_steps, decay_steps=cfg.decay_steps
        )
        self.pipeline.register_optimizer(
            "adamw",
            optax.chain(optax.clip_by_global_norm(1.0),
                        optax.adamw(schedule, b2=0.95, weight_decay=0.1)),
            scheduler=schedule,
        )
        if cfg.global_batch % runtime.world_size():
            raise ValueError(
                f"--global-batch {cfg.global_batch} must divide evenly across "
                f"{runtime.world_size()} processes"
            )
        per_host = cfg.global_batch // runtime.world_size()
        from dmlcloud_tpu.data import markov_tokens

        # per-rank seed for DISTINCT sequences, shared table_seed so all 16
        # hosts draw from the same successor table (one learnable chain)
        toks = markov_tokens(model_cfg.vocab_size, per_host * cfg.steps_per_epoch,
                             cfg.seq_len, seed=runtime.rank(), table_seed=0)
        self.pipeline.register_dataset(
            "train",
            [toks[i * per_host:(i + 1) * per_host] for i in range(cfg.steps_per_epoch)],
            verbose=False,
        )

    def checkpoint_every_steps(self):
        return int(self.config.get("save_every_steps", 0))

    def gradient_accumulation(self):
        return int(self.config.get("grad_accum", 1))

    def step_flops(self):
        import jax.tree_util as jtu

        # 6*params*tokens, embedding lookups excluded (PaLM convention)
        n = sum(int(x.size) for x in jtu.tree_leaves(self.state.params)) - int(
            self.state.params["embed"]["embedding"].size
        )
        return 6.0 * n * self.config.global_batch * self.config.seq_len

    def step(self, state, batch):
        chunk = int(self.config.get("chunked_loss", 0))
        if chunk > 0:
            hidden = state.apply_fn({"params": state.params}, batch, return_hidden=True)
            return chunked_lm_loss(
                hidden, state.params["lm_head"]["kernel"], batch, vocab_chunk=chunk
            )
        return lm_loss(state.apply_fn({"params": state.params}, batch), batch)

    def val_epoch(self):  # pretrain recipe: train metrics only
        pass


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=sorted(PRESETS), default="8b")
    ap.add_argument("--toy", action="store_true", help="tiny model + tiny batch (sets --preset toy)")
    ap.add_argument("--mesh", type=str, default="data=2,fsdp=32",
                    help="v5p-64 default; use data=2,fsdp=4 for the 8-device CPU mesh")
    ap.add_argument("--global-batch", type=int, default=128)
    ap.add_argument("--seq-len", type=int, default=4096)
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--steps-per-epoch", type=int, default=200,
                    help="synthetic-data epoch length (a real run sizes this from the dataset)")
    ap.add_argument("--attn", choices=["dot", "flash"], default="flash")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--chunked-loss", type=int, default=0, metavar="CHUNK")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--checkpoint-dir", type=str, default=None,
                    help="local path or gs://bucket/prefix (Orbax writes shards directly)")
    ap.add_argument("--save-every-steps", type=int, default=250)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    if args.toy:
        args.preset = "toy"
        args.global_batch = min(args.global_batch, 16)
        args.seq_len = min(args.seq_len, 64)
        args.steps_per_epoch = min(args.steps_per_epoch, 4)
        args.epochs = min(args.epochs, 2)
        args.attn = "dot"  # the Pallas kernel's CPU interpret mode is slow

    init_auto(verbose=True)

    steps_total = args.epochs * args.steps_per_epoch
    config = {
        "preset": args.preset,
        "global_batch": args.global_batch,
        "seq_len": args.seq_len,
        "steps_per_epoch": args.steps_per_epoch,
        "attn": args.attn,
        "lr": args.lr,
        "warmup_steps": max(steps_total // 50, 1),
        "decay_steps": steps_total,
        "remat": args.remat,
        "chunked_loss": args.chunked_loss,
        "grad_accum": args.grad_accum,
        "save_every_steps": args.save_every_steps,
        "seed": 0,
    }
    pipeline = dml.TrainingPipeline(config, name=f"llama-{args.preset}")
    axes = parse_mesh_axes(args.mesh)
    pipeline.set_mesh(axes)
    if args.checkpoint_dir:
        pipeline.enable_checkpointing(args.checkpoint_dir, resume=args.resume)
        # elastic resume (doc/elasticity.md): scheduler eviction drains at
        # the next step-save boundary, commits the state, writes the requeue
        # verdict; the requeued run restores onto WHATEVER mesh it gets
        # (signals=None = SIGTERM/SIGINT + SIGUSR1 under Slurm --signal)
        pipeline.enable_preemption_handling(signals=None)
    stage = LlamaStage()
    pipeline.append_stage(stage, max_epochs=args.epochs)
    pipeline.run()
    return stage


if __name__ == "__main__":
    main()

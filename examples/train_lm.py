"""Decoder-LM pretraining through the pipeline — the transformer-family
counterpart of examples/mnist.py (the reference ships only MNIST examples;
this one exercises the framework's mesh/sharding surface: dp, fsdp, tp via
T5X-style partition rules, and the flash/ring attention paths).

Run (single host; any chip count — the mesh folds over what's there):
    python examples/train_lm.py --preset tiny --epochs 2
    python examples/train_lm.py --preset small --mesh data=2,fsdp=4 --attn flash
"""

import argparse

import numpy as np
import optax

import dmlcloud_tpu as dml
from dmlcloud_tpu.models.transformer import (
    DecoderLM,
    TransformerConfig,
    llama_partition_rules,
    lm_loss,
)
from dmlcloud_tpu.parallel import init_auto, parse_mesh_axes

PRESETS = {
    "tiny": dict(num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16, hidden_dim=64, mlp_dim=160),
    "small": dict(num_layers=8, num_heads=8, num_kv_heads=4, head_dim=64, hidden_dim=512, mlp_dim=1408),
    "1b": dict(num_layers=24, num_heads=16, num_kv_heads=8, head_dim=128, hidden_dim=2048, mlp_dim=5632),
}


from dmlcloud_tpu.data import markov_tokens as synthetic_tokens  # noqa: E402 — learnable corpus


class LMStage(dml.TrainValStage):
    def pre_stage(self):
        cfg = self.config
        model_cfg = TransformerConfig(
            vocab_size=cfg.vocab_size,
            max_seq_len=cfg.seq_len,
            attn_impl=cfg.attn,
            tie_embeddings=bool(cfg.get("tie_embeddings", False)),
            remat=bool(cfg.get("remat", False)),
            sliding_window=cfg.get("window"),
            # ring and flash attention under plain jit shard_map themselves
            # over the mesh (ring splits the sequence; the flash kernel cannot
            # be partitioned by XLA); dot is mesh-agnostic
            mesh=self.mesh if cfg.attn in ("ring", "flash") else None,
            **PRESETS[cfg.preset],
        )
        model = DecoderLM(model_cfg)
        self.model = model  # kept for post-run sampling (--sample)

        if cfg.get("pack", False):
            # variable-length corpus packed into full rows: the packer emits
            # {"tokens", "segment_ids"} and the step routes them through the
            # segment-isolated attention + masked loss path
            from dmlcloud_tpu.data import pack_sequences

            rng = np.random.RandomState(1)
            # ids shifted +1 below so pad id 0 never collides with a token
            full = synthetic_tokens(cfg.vocab_size - 1, cfg.n_seqs, cfg.seq_len)
            pieces = [row[: rng.randint(cfg.seq_len // 4, cfg.seq_len + 1)] + 1 for row in full]
            rows = list(pack_sequences(pieces, cfg.seq_len))
            tokens = np.stack([np.stack([r["tokens"], r["segment_ids"]]) for r in rows])  # [N, 2, T]
            self.sample_prompt = full[:2, :16] + 1  # corpus-distribution prompt, shifted like training
        else:
            tokens = synthetic_tokens(cfg.vocab_size, cfg.n_seqs, cfg.seq_len)
            self.sample_prompt = tokens[:2, :16].copy()
        n_val = max(cfg.batch_size, len(tokens) // 10)
        bs = cfg.batch_size
        if (len(tokens) - n_val) < bs:
            raise ValueError(
                f"{len(tokens)} rows after packing/splitting leave fewer than one "
                f"train batch (batch_size={bs}, val={n_val}); raise --n-seqs or lower --batch-size"
            )

        def loader(data):
            class Loader:
                def __iter__(self):
                    for i in range(0, len(data) - bs + 1, bs):
                        yield data[i : i + bs]

                def __len__(self):
                    return len(data) // bs

            return Loader()

        self.pipeline.register_dataset("train", loader(tokens[n_val:]))
        self.pipeline.register_dataset("val", loader(tokens[:n_val]))
        self.pipeline.register_model(
            "lm",
            model,
            init_args=(np.zeros((1, 8), np.int32),),
            sharding=llama_partition_rules(),
        )
        schedule = optax.warmup_cosine_decay_schedule(0.0, cfg.lr, 20, 2000)
        self.pipeline.register_optimizer("adamw", optax.adamw(schedule), scheduler=schedule)

    def gradient_clip(self):
        return 1.0

    def ema_decay(self):
        return float(self.config.get("ema", 0.0))

    def checkpoint_every_steps(self):
        return int(self.config.get("save_every_steps", 0))

    def step_flops(self):
        # 6 * params * tokens per global batch (PaLM convention); reported
        # as misc/mfu in the table/wandb/tensorboard
        if not self.config.get("mfu", False):
            return 0.0
        import jax.tree_util as jtu

        n_params = sum(int(x.size) for x in jtu.tree_leaves(self.state.params))
        return 6.0 * n_params * self.config.batch_size * self.config.seq_len

    def step(self, state, batch):
        chunk = int(self.config.get("chunked_loss", 0))
        if self.config.get("pack", False):
            toks, segs = batch[:, 0], batch[:, 1]
        else:
            toks, segs = batch, None
        if chunk > 0:
            from dmlcloud_tpu.models.transformer import chunked_lm_loss

            hidden = state.apply_fn(
                {"params": state.params}, toks, segment_ids=segs, return_hidden=True
            )
            if self.model.cfg.tie_embeddings:
                head = state.params["embed"]["embedding"].T
            else:
                head = state.params["lm_head"]["kernel"]
            return chunked_lm_loss(
                hidden, head, toks, vocab_chunk=chunk, segment_ids=segs,
            )
        logits = state.apply_fn({"params": state.params}, toks, segment_ids=segs)
        return lm_loss(logits, toks, segment_ids=segs)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--preset", choices=sorted(PRESETS), default="tiny")
    parser.add_argument("--epochs", type=int, default=2)
    parser.add_argument("--batch-size", type=int, default=8)
    parser.add_argument("--seq-len", type=int, default=128)
    parser.add_argument("--vocab-size", type=int, default=512)
    parser.add_argument("--n-seqs", type=int, default=512)
    parser.add_argument("--lr", type=float, default=3e-4)
    parser.add_argument("--attn", choices=["dot", "flash", "ring"], default="dot")
    parser.add_argument("--window", type=int, default=None, help="sliding-window attention width")
    parser.add_argument("--pack", action="store_true", help="pack a variable-length corpus (segment_ids path)")
    parser.add_argument("--remat", action="store_true", help="recompute blocks in the backward pass (long-context memory)")
    parser.add_argument("--tie-embeddings", action="store_true", help="share the embedding matrix with the LM head")
    parser.add_argument("--mesh", type=str, default=None, help="e.g. data=2,fsdp=4")
    parser.add_argument("--checkpoint-dir", type=str, default=None)
    parser.add_argument("--ema", type=float, default=0.0, help="param EMA decay (0 off); validation uses the average")
    parser.add_argument("--save-every-steps", type=int, default=0, help="mid-epoch step saves (resumable mid-epoch)")
    parser.add_argument("--mfu", action="store_true", help="track misc/mfu from the 6ND estimate")
    parser.add_argument(
        "--chunked-loss", type=int, default=0, metavar="CHUNK",
        help="vocab chunk for chunked_lm_loss (0 = full logits); big-vocab memory lever",
    )
    parser.add_argument(
        "--sample", type=int, default=0, metavar="N",
        help="after training, greedy-decode N tokens from a corpus prompt (KV-cache generate)",
    )
    args = parser.parse_args()

    if args.pack and args.attn == "ring":
        parser.error("--pack (segment_ids) is not supported with --attn ring")

    init_auto(verbose=True)

    config = {
        "preset": args.preset,
        "batch_size": args.batch_size,
        "seq_len": args.seq_len,
        "vocab_size": args.vocab_size,
        "n_seqs": args.n_seqs,
        "lr": args.lr,
        "attn": args.attn,
        "tie_embeddings": args.tie_embeddings,
        "remat": args.remat,
        "window": args.window,
        "pack": args.pack,
        "ema": args.ema,
        "save_every_steps": args.save_every_steps,
        "mfu": args.mfu,
        "chunked_loss": args.chunked_loss,
        "seed": 0,
    }
    pipeline = dml.TrainingPipeline(config, name=f"lm-{args.preset}")
    if args.mesh:
        axes = parse_mesh_axes(args.mesh)
        pipeline.set_mesh(axes)
    if args.checkpoint_dir:
        pipeline.enable_checkpointing(args.checkpoint_dir)
    stage = LMStage()
    pipeline.append_stage(stage, max_epochs=args.epochs)
    pipeline.run()

    if args.sample > 0:
        from dmlcloud_tpu.models.generate import generate
        from dmlcloud_tpu.parallel import runtime

        if runtime.world_size() > 1:
            # multi-controller decode would need globally-replicated prompt
            # arrays; the flag is a single-process demo of the decode path
            if runtime.rank() == 0:
                print("--sample is a single-process demo; skipping under multi-process runs")
        else:
            out = generate(stage.model, stage.state.params, stage.sample_prompt, max_new_tokens=args.sample)
            for row, cont in zip(stage.sample_prompt.tolist(), np.asarray(out).tolist()):
                print(f"prompt {row} -> {cont}")


if __name__ == "__main__":
    main()

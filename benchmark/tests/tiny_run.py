"""``python tiny_run.py <tree> <fault> <run.py arguments>``: run.py's ``main``
with the look for a chip skipped (a step of the test, not a switch of the
program or of the harness) and, where asked, the timed path broken underneath:

- ``token_altered``: every token the engine emits is changed where it is produced;
- ``unchanged_state``: the train step returns its state as it was;
- ``half_batch``: the loss leaves out the second half of the rows and takes the mean over the rest.
"""

import importlib.util
import os
import sys

tree, fault, argv = sys.argv[1], sys.argv[2], sys.argv[3:]
sys.path.insert(0, tree)
spec = importlib.util.spec_from_file_location("bench_run", os.path.join(tree, "benchmark", "run.py"))
run = importlib.util.module_from_spec(spec)
spec.loader.exec_module(run)

import jax  # noqa: E402

from benchmark import peaks  # noqa: E402

run.devices_for = lambda chips: jax.devices()[:chips]
peaks.PEAKS[jax.devices()[0].device_kind] = peaks.PEAKS["TPU v5 lite"]

if fault == "token_altered":
    from dmlcloud_tpu.serve import engine as engine_mod

    emit = engine_mod.ServeEngine._emit
    engine_mod.ServeEngine._emit = lambda self, seq, tok, now: emit(self, seq, (tok + 1) % self.model.cfg.vocab_size, now)
elif fault == "unchanged_state":
    from dmlcloud_tpu import train_state

    train_state.TrainState.apply_gradients = lambda self, grads: self.replace(step=self.step + 1)
elif fault == "half_batch":
    from dmlcloud_tpu.models import transformer

    whole = transformer.lm_loss
    transformer.lm_loss = lambda logits, tokens, *a, **k: whole(logits[: len(tokens) // 2], tokens[: len(tokens) // 2], *a, **k)
elif fault != "none":
    raise SystemExit(f"unknown fault {fault!r}")

sys.exit(run.main(argv))

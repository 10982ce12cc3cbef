"""Parallelism tour: every intra-trial mode on one mesh, end to end.

The platform's trial compute runs over a ``("dp", "pp", "ep", "sp",
"tp")`` mesh built from the trial's chip group (SURVEY.md §2.9; absent
upstream — trial-level parallelism was Rafiki's only axis). This tour
trains the SAME transformer tagger under each mode and prints the
scores, demonstrating that a model knob — not a rewrite — selects the
strategy:

- dp (always on): batch data parallelism; grads psum over ICI.
- sp=ring:     sequence shards rotate K/V one ICI neighbour per step.
- sp=alltoall: Ulysses — one all_to_all to head-sharding and back.
- ep:          Switch-MoE FFN, expert stack sharded; XLA derives the
               dispatch/combine all-to-alls from parameter shardings.
- pp:          GPipe microbatch pipeline over the encoder blocks.
- pp x sp:     ring attention inside the pipelined stages.
- pp x ep:     MoE stages with each stage's expert slice over ep.

Run on the 8-device virtual CPU mesh (no TPU needed):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python examples/scripts/parallelism_tour.py

On a real slice the same knobs map onto ICI; nothing changes but speed.
"""

import tempfile


def main() -> None:
    import jax

    from rafiki_tpu.datasets import make_synthetic_corpus_dataset
    from rafiki_tpu.models import JaxTransformerTagger

    n = len(jax.devices())
    if n < 2 or n % 2:
        raise SystemExit(f"need an even device count >= 2, have {n} "
                         "(set XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=8)")

    with tempfile.TemporaryDirectory() as tmp:
        train, val = make_synthetic_corpus_dataset(
            tmp, n_train=96, n_val=24, vocab=64, n_tags=4, max_len=24)
        base = dict(d_model=64, n_heads=4, n_layers=2,
                    learning_rate=1e-2, batch_size=16, max_epochs=8,
                    max_len=32, dropout=0.0, vocab_size=1024)
        modes = [
            ("dp only", {}),
            ("sp ring", dict(sequence_parallel=2)),
            ("sp alltoall", dict(sequence_parallel=2,
                                 sp_schedule="alltoall")),
            ("ep moe", dict(moe_experts=4, expert_parallel=2)),
            ("pp gpipe", dict(pipeline_parallel=2)),
        ]
        if n % 4 == 0:
            # Composed modes need 4 mesh cells beyond dp.
            modes += [
                ("pp x sp", dict(pipeline_parallel=2,
                                 sequence_parallel=2)),
                ("pp x ep", dict(pipeline_parallel=2, moe_experts=4,
                                 expert_parallel=2)),
            ]
        for name, extra in modes:
            model = JaxTransformerTagger(**base, **extra)
            shape = dict(model.mesh.shape)
            model.train(train)
            score = float(model.evaluate(val))
            model.destroy()
            axes = "x".join(f"{a}{v}" for a, v in shape.items() if v > 1)
            print(f"{name:12s} mesh[{axes:12s}] token-acc={score:.4f}",
                  flush=True)
    print("PARALLELISM TOUR OK")


if __name__ == "__main__":
    from rafiki_tpu.jaxenv import ensure_platform

    # Resolve the JAX platform up front: JAX_PLATFORMS=cpu pins the CPU;
    # anything else must find a TPU or the script refuses to run.
    ensure_platform()
    main()

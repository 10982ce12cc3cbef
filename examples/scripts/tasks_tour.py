"""Task tour: every supported task type end-to-end in one process.

Parity: SURVEY.md §2 "Constants" task types — IMAGE_CLASSIFICATION,
POS_TAGGING, TABULAR_CLASSIFICATION, TABULAR_REGRESSION each run the full
propose → train → evaluate → predict cycle through ``test_model_class``
(the §3.4 model-developer seam) on synthetic data.

    python examples/scripts/tasks_tour.py
"""

import tempfile


def main() -> None:
    from rafiki_tpu.constants import TaskType
    from rafiki_tpu.datasets import (make_synthetic_corpus_dataset,
                                     make_synthetic_image_dataset,
                                     make_synthetic_tabular_dataset)
    from rafiki_tpu.model import (load_corpus_dataset, load_image_dataset,
                                  load_tabular_dataset, test_model_class)
    from rafiki_tpu.models import (JaxFeedForward, JaxPosTagger,
                                   JaxTabMlpClf, JaxTabMlpReg,
                                   JaxTransformerTagger)

    workdir = tempfile.mkdtemp(prefix="rafiki_tour_")

    # 1. Image classification
    tr, va = make_synthetic_image_dataset(workdir, n_train=2048, n_val=256,
                                          image_shape=(28, 28, 1),
                                          n_classes=10)
    r = test_model_class(
        JaxFeedForward, TaskType.IMAGE_CLASSIFICATION, tr, va,
        test_queries=[load_image_dataset(va).images[0]],
        knobs={"hidden_layer_count": 2, "hidden_layer_units": 64,
               "learning_rate": 1e-3, "batch_size": 64, "max_epochs": 5})
    print(f"IMAGE_CLASSIFICATION  JaxFeedForward  acc={r.score:.3f}")

    # 2. POS tagging
    tr, va = make_synthetic_corpus_dataset(workdir, n_train=512, n_val=128,
                                           vocab=200, n_tags=8)
    r = test_model_class(
        JaxPosTagger, TaskType.POS_TAGGING, tr, va,
        test_queries=load_corpus_dataset(va).sentences[:2],
        knobs={"embed_dim": 32, "hidden": 64, "learning_rate": 5e-3,
               "batch_size": 32, "max_epochs": 8, "max_len": 64,
               "vocab_size": 16384})
    print(f"POS_TAGGING           JaxPosTagger    token-acc={r.score:.3f}")

    # 2b. POS tagging with the attention-ops Transformer (flash/ring)
    r = test_model_class(
        JaxTransformerTagger, TaskType.POS_TAGGING, tr, va,
        test_queries=load_corpus_dataset(va).sentences[:2],
        knobs={"d_model": 64, "n_heads": 2, "n_layers": 2,
               "learning_rate": 1e-2, "batch_size": 32, "max_epochs": 15,
               "max_len": 64, "dropout": 0.0, "vocab_size": 16384,
               "sequence_parallel": 1})
    print(f"POS_TAGGING           JaxTransformerTagger token-acc={r.score:.3f}")

    # 3. Tabular classification
    tr, va = make_synthetic_tabular_dataset(workdir, n_train=1024,
                                            n_val=256, n_features=10,
                                            n_classes=4, name="tc")
    r = test_model_class(
        JaxTabMlpClf, TaskType.TABULAR_CLASSIFICATION, tr, va,
        test_queries=[load_tabular_dataset(va).features[0]],
        knobs={"hidden": 64, "depth": 2, "learning_rate": 5e-3,
               "batch_size": 64, "max_epochs": 15})
    print(f"TABULAR_CLASSIFICATION JaxTabMlpClf   acc={r.score:.3f}")

    # 4. Tabular regression
    tr, va = make_synthetic_tabular_dataset(workdir, n_train=1024,
                                            n_val=256, n_features=10,
                                            n_classes=0, name="treg")
    r = test_model_class(
        JaxTabMlpReg, TaskType.TABULAR_REGRESSION, tr, va,
        test_queries=[load_tabular_dataset(va).features[0]],
        knobs={"hidden": 64, "depth": 2, "learning_rate": 5e-3,
               "batch_size": 64, "max_epochs": 15})
    print(f"TABULAR_REGRESSION    JaxTabMlpReg    R2={r.score:.3f}")
    print("TASKS TOUR OK")


if __name__ == "__main__":
    from rafiki_tpu.jaxenv import ensure_platform

    # Resolve the JAX platform up front: JAX_PLATFORMS=cpu pins the CPU;
    # anything else must find a TPU or the script refuses to run.
    ensure_platform()
    main()

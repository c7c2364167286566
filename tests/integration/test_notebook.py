"""Notebook-level integration: execute the demo notebook through a real
Jupyter kernel with nbclient and assert on the streamed, rank-tagged
outputs — the test tier the reference only declared in packaging
(reference: pyproject.toml:36-42 lists nbformat+nbclient; SURVEY §4).
"""

import os

import pytest

pytestmark = [pytest.mark.integration, pytest.mark.slow]

REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
NOTEBOOK = os.path.join(REPO_ROOT, "examples", "00_quickstart.ipynb")


def _all_text(nb):
    chunks = []
    for cell in nb.cells:
        for out in cell.get("outputs", []):
            if out.get("output_type") == "stream":
                chunks.append(out.get("text", ""))
            elif out.get("output_type") == "execute_result":
                chunks.append(out.get("data", {}).get("text/plain", ""))
            elif out.get("output_type") == "error":
                chunks.append("\n".join(out.get("traceback", [])))
    return "\n".join(chunks)


def _assert_clean(nb):
    errors = [out for cell in nb.cells
              for out in cell.get("outputs", [])
              if out.get("output_type") == "error"]
    assert not errors, errors


def _execute_notebook(filename: str, *, timeout: int,
                      env_patch: dict | None = None):
    """Run one example notebook through a real Jupyter kernel with the
    repo on PYTHONPATH (kernel + its spawned workers must import this
    checkout); env is patched for the duration and restored."""
    nbclient = pytest.importorskip("nbclient")
    import nbformat

    nb = nbformat.read(os.path.join(REPO_ROOT, "examples", filename),
                       as_version=4)
    env_patch = dict(env_patch or {})
    env_patch["PYTHONPATH"] = (REPO_ROOT + os.pathsep
                               + os.environ.get("PYTHONPATH", ""))
    old = {k: os.environ.get(k) for k in env_patch}
    os.environ.update(env_patch)
    try:
        client = nbclient.NotebookClient(
            nb, timeout=timeout, kernel_name="python3",
            resources={"metadata": {"path": REPO_ROOT}})
        client.execute()
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return nb


@pytest.fixture(scope="module")
def executed_nb():
    return _execute_notebook(
        "00_quickstart.ipynb", timeout=300,
        env_patch={"NBD_NOTEBOOK_BACKEND": "cpu",
                   "NBD_NOTEBOOK_WORKERS": "2"})


def test_notebook_runs_clean(executed_nb):
    _assert_clean(executed_nb)


def test_notebook_rank_tagged_output(executed_nb):
    text = _all_text(executed_nb)
    assert "Rank 0" in text and "Rank 1" in text


def test_notebook_collective_result(executed_nb):
    # all_reduce of ones*(rank+1) over 2 ranks -> 3.0 on every rank.
    assert "3.0" in _all_text(executed_nb)


def test_notebook_training_progresses(executed_nb):
    text = _all_text(executed_nb)
    assert "step 0: loss" in text and "step 4: loss" in text
    assert "eval loss" in text


def test_notebook_broadcast_matches(executed_nb):
    # The cell after the %%rank[0] creation echoes W.sum() per rank;
    # both ranks must show the identical value.
    import re

    assert "created on rank 0 only" in _all_text(executed_nb)
    cell = next(c for c in executed_nb.cells
                if c.cell_type == "code" and "broadcast(W" in c.source)
    text = "\n".join(o.get("text", "") for o in cell["outputs"])
    sums = re.findall(r"Rank (\d):\s*\n(-?\d+\.\d+)", text)
    assert sorted(r for r, _ in sums) == ["0", "1"], text
    assert len({v for _, v in sums}) == 1, text


def test_notebook_no_worker_errors(executed_nb):
    text = _all_text(executed_nb)
    assert "❌" not in text and "Traceback" not in text, text[-2000:]


def test_notebook_checkpoint_restore_exact(executed_nb):
    text = _all_text(executed_nb)
    assert "ranks saved" in text and "ranks restored" in text
    assert "(exact)" in text


@pytest.fixture(scope="module")
def executed_parallelism_nb():
    # The notebook forces its own cpu/8-device env internally.
    return _execute_notebook("01_parallelism.ipynb", timeout=600)


def test_parallelism_notebook_runs_clean(executed_parallelism_nb):
    _assert_clean(executed_parallelism_nb)


def test_parallelism_notebook_strategies_exact(executed_parallelism_nb):
    text = _all_text(executed_parallelism_nb)
    assert "ring" in text and "ulysses" in text
    assert "pipeline max |err|" in text
    assert "MoE loss over dp×ep mesh" in text
    assert "moment sharding" in text and "dp" in text
    assert "greedy:" in text and "top-k/p:" in text
    assert "ring-attention train step over dp×sp×tp" in text
    assert "int8 vs bf16 top-1 agreement" in text
    assert "LoRA:" in text and "adapter params" in text
    assert "FSDP train step: loss" in text and "sharded 4-way" in text
    assert "speculative == target greedy: True" in text
    assert "self-draft mean accepted/round: 3.00" in text
    assert "batched speculative (B=2) == batched greedy: True" in text
    assert "1F1B vs GPipe grads match: True" in text
    assert "buffer 7 deep" in text
    assert "sparse MoE dispatch == dense: True" in text
    assert "3/8 hops pay compute+ppermute" in text


@pytest.fixture(scope="module")
def executed_finetune_nb(tmp_path_factory):
    """The reference's flagship journey (00_accelerate.ipynb): local
    SmolLM2-135M-architecture checkpoint -> load_hf_pretrained ->
    packed local-text dataset -> cell-by-cell DDP fine-tune ->
    generation.  (Checkpoint is locally constructed: zero-egress
    environment.)  Per-run temp dirs: no /tmp litter
    or cross-run races on the ~0.5G checkpoint."""
    tmp = tmp_path_factory.mktemp("finetune_nb")
    return _execute_notebook(
        "02_finetune.ipynb", timeout=600,
        env_patch={"NBD_NOTEBOOK_BACKEND": "cpu",
                   "NBD_NOTEBOOK_WORKERS": "2",
                   "NBD_NOTEBOOK_CKPT_DIR": str(tmp / "ckpt"),
                   "NBD_NOTEBOOK_CK_OUT": str(tmp / "ck_out")})


def test_finetune_notebook_runs_clean(executed_finetune_nb):
    _assert_clean(executed_finetune_nb)


def test_finetune_notebook_journey(executed_finetune_nb):
    """The full accelerate-style journey, rank-tagged: checkpoint
    built, loaded on both ranks, real-text dataset packed, DDP loss
    improves, generation produced, state checkpointed."""
    text = _all_text(executed_finetune_nb)
    assert "SmolLM2-135M-architecture" in text
    # 134.5M torch params; the tied lm_head materializes as embed.T in
    # the JAX pytree -> 162.8M leaves.
    assert "loaded 162.8M params, d_model=576, layers=30" in text
    assert "Rank 0" in text and "Rank 1" in text
    assert "step 0: loss" in text and "step 3: loss" in text
    assert "improved" in text and "NOT improved" not in text
    assert "continuation" in text
    assert "ranks saved" in text
    assert "❌" not in text

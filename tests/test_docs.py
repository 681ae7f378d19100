"""Docs subsystem consistency (tier-1 wrapper over tests/check_docs.py).

The CI `docs` job runs ``python tests/check_docs.py`` standalone (no
jax needed); these tests run the same checks inside the normal suite
and additionally assert the ast-parsed backend list matches the live
module, so the text-level parse can't drift from the real constant.
"""
import check_docs


def test_markdown_links_resolve():
    errors, checked = check_docs.check_links()
    assert not errors, "\n".join(errors)
    assert checked > 0, "link scan found no intra-repo markdown links"


def test_kernels_doc_backends_in_sync():
    assert check_docs.check_backend_sync() == []


def test_kernels_doc_lowering_column_in_sync():
    assert check_docs.check_lowering_sync() == []


def test_lowering_artifact_covers_every_backend():
    # The committed BENCH_lowering.json must have a verdict for every
    # backend the docs matrix claims a lowering status for.
    from repro.kernels.mttkrp import ops as kops
    status = check_docs.lowering_status()
    assert set(status) == set(kops.BACKENDS)
    assert all(status.values()), status


def test_ast_parse_matches_live_module():
    from repro.kernels.mttkrp import ops as kops
    assert check_docs.ops_backends() == kops.BACKENDS


def test_observability_doc_scopes_in_sync():
    assert check_docs.check_scope_sync() == []


def test_scope_literal_matches_live_module():
    from repro.obs import scopes
    assert check_docs.literal_tuple(check_docs.SCOPES_PATH, "SCOPES") \
        == scopes.SCOPES

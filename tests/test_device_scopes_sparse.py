"""``tests/test_device_scopes.py``'s three cases of the sparse kind
(``latent-sparse-debug``), in a file of their own so that a worker compiles one
heavy stack's two forms and another the other's: the checks are that file's."""

import pytest

from test_device_scopes import (check_passes, check_paths, check_scopes_alone,  # noqa: F401
                                step_texts)


@pytest.mark.parametrize("kind", ["sparse"])
def test_every_scoped_op_carries_the_pass_its_op_name_says(step_texts, kind):  # noqa: F811
    check_passes(step_texts(kind))


@pytest.mark.parametrize("kind", ["sparse"])
def test_every_op_a_model_scope_issued_carries_its_path(step_texts, kind):  # noqa: F811
    check_paths(step_texts(kind), kind)


@pytest.mark.parametrize("kind", ["sparse"])
def test_scopes_change_names_metadata_and_the_attribute_only(monkeypatch, step_texts, kind):  # noqa: F811
    check_scopes_alone(monkeypatch, step_texts, kind)

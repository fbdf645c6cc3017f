"""Meta-lint over the analyzer itself: every rule id in
``analysis.findings.RULES`` must be documented in the rule catalog
table of ``docs/development/static_analysis.md`` AND exercised by at
least one seeded fixture or live-flagging test — the next FML404-style
rule cannot land undocumented or untested without failing here."""

import os
import re

from flinkml_tpu.analysis.findings import RULES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = os.path.join(REPO, "docs", "development", "static_analysis.md")
TESTS = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(TESTS, "analysis_fixtures")


def _documented_rules():
    """Rule ids appearing as rows of the docs catalog table
    (``| FML101 | error | ... |``)."""
    with open(DOCS) as fh:
        text = fh.read()
    return set(re.findall(r"^\|\s*(FML\d{3})\s*\|", text, re.MULTILINE))


def test_every_rule_has_a_docs_catalog_row():
    documented = _documented_rules()
    missing = sorted(set(RULES) - documented)
    assert not missing, (
        f"rules missing from the docs/development/static_analysis.md "
        f"catalog table: {missing}"
    )
    stale = sorted(documented - set(RULES))
    assert not stale, (
        f"docs catalog rows without a RULES entry (removed rule ids are "
        f"permanent — mark them retired instead of deleting): {stale}"
    )


def test_cli_rules_catalog_is_the_docs_table(capsys):
    """What ``python -m flinkml_tpu.analysis --rules`` prints and the
    docs table agree row for row."""
    from flinkml_tpu.analysis.__main__ import main

    assert main(["--rules"]) == 0
    printed = set(re.findall(r"^(FML\d{3})\b", capsys.readouterr().out,
                             re.MULTILINE))
    assert printed == _documented_rules()


def test_every_rule_has_a_fixture_or_a_flagging_test():
    fixture_names = " ".join(os.listdir(FIXTURES)).lower()
    test_sources = ""
    for name in sorted(os.listdir(TESTS)):
        if name.startswith("test_") and name.endswith(".py"):
            with open(os.path.join(TESTS, name)) as fh:
                test_sources += fh.read()
    unexercised = sorted(
        rule for rule in RULES
        if rule.lower() not in fixture_names
        and f'"{rule}"' not in test_sources
        and f"'{rule}'" not in test_sources
    )
    assert not unexercised, (
        f"rules with neither a seeded fixture (tests/analysis_fixtures/"
        f"*{'{'}rule{'}'}*) nor a test referencing them: {unexercised}"
    )

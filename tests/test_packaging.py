"""Packaging metadata stays wired to the code: console-script target,
package-data globs, and the deploy/Docker entrypoint contract.
"""
from __future__ import annotations

import os
import tomllib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _pyproject():
    with open(os.path.join(REPO, "pyproject.toml"), "rb") as f:
        return tomllib.load(f)


def test_console_script_targets_cli_main():
    proj = _pyproject()
    target = proj["project"]["scripts"]["foremast-tpu"]
    mod_name, func = target.split(":")
    import importlib

    mod = importlib.import_module(mod_name)
    assert callable(getattr(mod, func))


def test_package_data_files_exist():
    proj = _pyproject()
    data = proj["tool"]["setuptools"]["package-data"]
    import glob

    for pkg, patterns in data.items():
        pkg_dir = os.path.join(REPO, *pkg.split("."))
        for pattern in patterns:
            assert glob.glob(os.path.join(pkg_dir, pattern)), (pkg, pattern)


def test_dockerfile_entrypoint_matches_manifests():
    with open(os.path.join(REPO, "Dockerfile")) as f:
        docker = f.read()
    assert 'ENTRYPOINT ["foremast-tpu"]' in docker
    assert 'CMD ["serve"]' in docker
    # the stack manifests select processes via bare args on this entrypoint
    import yaml

    for name, expect in (("20-runtime.yaml", "serve"), ("30-operator.yaml", "operator")):
        with open(os.path.join(REPO, "deploy", "stack", name)) as f:
            docs = list(yaml.safe_load_all(f))
        dep = next(d for d in docs if d["kind"] == "Deployment")
        [container] = dep["spec"]["template"]["spec"]["containers"]
        assert container["args"] == [expect], name

"""spmdlint.toml loading + waiver matching.

Waiver entries silence one rule at one site::

    [[waiver]]
    rule = "SPMD001"
    path = "src/repro/pkg/module.py"
    symbol = "exchange"                 # optional; omit = whole file
    reason = "why this site is sanctioned"

``path`` matches by normalized suffix, so waivers keep working whether
the linter is invoked from the repo root or with absolute paths.
``symbol`` matches the diagnostic's in-file qualname exactly or as a
trailing component (``local.body`` matches ``symbol = "body"``). The
optional ``[spmd] axes`` array overrides the declared axis-name universe
for SPMD002.

Python 3.10 has no ``tomllib``; ``_parse_mini_toml`` covers the subset
this file needs (tables, arrays of tables, string/number/bool/array
values, comments).
"""
from __future__ import annotations

import os
from dataclasses import dataclass


@dataclass(frozen=True)
class Waiver:
    rule: str
    path: str
    symbol: str | None = None
    reason: str = ""

    def matches(self, diag) -> bool:
        if self.rule != diag.rule:
            return False
        want = self.path.replace(os.sep, "/").lstrip("./")
        got = diag.path.replace(os.sep, "/")
        if not (got == want or got.endswith("/" + want)):
            return False
        if self.symbol is None:
            return True
        sym = diag.symbol
        return sym == self.symbol or sym.endswith("." + self.symbol)


@dataclass
class Config:
    waivers: list[Waiver]
    axes: frozenset[str] | None = None   # None = rule default
    source: str | None = None


def load_config(path: str | None) -> Config:
    """Load ``spmdlint.toml``; a missing/None path is an empty config."""
    if path is None or not os.path.exists(path):
        return Config(waivers=[], source=None)
    with open(path, encoding="utf-8") as fh:
        text = fh.read()
    try:
        import tomllib
        data = tomllib.loads(text)
    except ModuleNotFoundError:
        data = _parse_mini_toml(text)
    waivers = []
    for entry in data.get("waiver", []):
        if "rule" not in entry or "path" not in entry:
            raise ValueError(
                f"{path}: every [[waiver]] needs 'rule' and 'path' keys, "
                f"got {sorted(entry)}")
        waivers.append(Waiver(rule=str(entry["rule"]),
                              path=str(entry["path"]),
                              symbol=entry.get("symbol"),
                              reason=str(entry.get("reason", ""))))
    axes = data.get("spmd", {}).get("axes")
    return Config(waivers=waivers,
                  axes=frozenset(axes) if axes is not None else None,
                  source=path)


def _parse_mini_toml(text: str) -> dict:
    """TOML subset: ``[table]`` / ``[[array-of-tables]]`` headers and
    ``key = value`` lines with string, integer, float, boolean, or flat
    string-array values. Enough for spmdlint.toml on Python < 3.11."""
    root: dict = {}
    current = root
    for lineno, raw in enumerate(text.splitlines(), 1):
        line = raw.strip()
        if not line or line.startswith("#"):
            continue
        if line.startswith("[[") and line.endswith("]]"):
            name = line[2:-2].strip()
            current = {}
            root.setdefault(name, []).append(current)
            continue
        if line.startswith("[") and line.endswith("]"):
            name = line[1:-1].strip()
            current = root.setdefault(name, {})
            continue
        if "=" not in line:
            raise ValueError(f"spmdlint.toml:{lineno}: expected key = "
                             f"value, got {raw!r}")
        key, _, rest = line.partition("=")
        current[key.strip()] = _parse_value(rest.strip(), lineno)
    return root


def _parse_value(token: str, lineno: int):
    if token.startswith('"'):
        end = _string_end(token, lineno)
        return token[1:end]
    if token.startswith("["):
        body = token[1:token.rindex("]")].strip()
        if not body:
            return []
        return [_parse_value(item.strip(), lineno)
                for item in _split_array(body)]
    if token in ("true", "false"):
        return token == "true"
    bare = token.split("#", 1)[0].strip()
    try:
        return int(bare)
    except ValueError:
        pass
    try:
        return float(bare)
    except ValueError:
        raise ValueError(f"spmdlint.toml:{lineno}: unsupported value "
                         f"{token!r}") from None


def _string_end(token: str, lineno: int) -> int:
    i = 1
    while i < len(token):
        if token[i] == "\\":
            i += 2
            continue
        if token[i] == '"':
            return i
        i += 1
    raise ValueError(f"spmdlint.toml:{lineno}: unterminated string")


def _split_array(body: str) -> list[str]:
    items, depth, start, in_str = [], 0, 0, False
    for i, ch in enumerate(body):
        if ch == '"' and (i == 0 or body[i - 1] != "\\"):
            in_str = not in_str
        elif not in_str:
            if ch == "[":
                depth += 1
            elif ch == "]":
                depth -= 1
            elif ch == "," and depth == 0:
                items.append(body[start:i])
                start = i + 1
    last = body[start:].strip()
    if last:
        items.append(last)
    return items

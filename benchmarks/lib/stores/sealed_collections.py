"""Two sealed templates built from the seed, one full and one small, for a
server whose volumes are laid out by collection: `full` and `small` are each a
`sealed_template` recipe. The traffic lays the server's volumes out as
`<collection>_<vid>.dat/.idx` links of the two (`link_volume`)."""

from __future__ import annotations

import os
import types

from . import _volume_writer, sealed_template


def build(recipe: dict, dirs, seed: int, pool_map, workers: int) -> dict:
    built = {}
    for name in ("full", "small"):
        sub = types.SimpleNamespace(scratch=os.path.join(dirs.scratch, name), data=dirs.data)
        os.makedirs(sub.scratch, exist_ok=True)
        built[name] = sealed_template.build(dict(recipe[name]), sub, seed, pool_map, workers)
    return {
        "kind": "sealed_collections", "full": built["full"], "small": built["small"],
        "dat_bytes": built["full"]["dat_bytes"], "small_dat_bytes": built["small"]["dat_bytes"],
        "needles": built["full"]["needles"], "small_needles": built["small"]["needles"],
    }


def modified_at(template: dict) -> int:
    """The second its last record is stamped with: what a volume's modification
    time is once it is loaded from these files (`_volume_writer.EPOCH_NS + i`)."""
    return (_volume_writer.EPOCH_NS + template["needles"] - 1) // 10**9


def link_volume(template: dict, data_dir: str, collection: str, vid: int) -> str:
    """Volume `vid` of `collection` as symbolic links of a template; its base name."""
    base = os.path.join(data_dir, f"{collection}_{vid}" if collection else str(vid))
    for ext in ("dat", "idx"):
        os.symlink(template[ext], f"{base}.{ext}")
    return base

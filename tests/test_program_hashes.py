"""The dense, Mixtral and OLMoE programs lower to the StableHLO they did
before the latent-attention family came (PR 30's parent, commit
69d2026), the latent-attention family's to what they did before the
hybrid family came (PR 32's parent, commit bdbf057), and the hybrid
family's three test sizes (a prefill chunk and a decode step each) to
what they did before its sorted-tile dispatch was lifted into
models/moe_tiles.py for a second caller (PR 42's parent, commit
3b42824): tools/hash_programs.py's digests, taken on those commits under
this suite's conftest (its XLA flags are part of the text). A PR
that means to change one of these programs replaces its digest, from a
run of the tool on itself, and says so.

PR 42 moved the Mixtral family's dropless ``_counted`` prefills (the
mask of real positions, no capacity factor, one device, more than one
position a row: every program an admission runs) from buckets onto
tiles. The tool did not lower those programs before; it does now, and
``PR42`` holds their four digests, taken on PR 42 itself:
``tiny-olmoe.prefill_counted`` and ``tiny-olmoe.prefill_chunk_counted``,
and ``tiny-moe``'s two with them, because tiny-moe registers no
capacity factor either and so is dropless as tiny-olmoe is. On the
parent the four read
``tiny-moe.prefill_counted`` 6575ab7a135b,
``tiny-moe.prefill_chunk_counted`` f1a2e6cc801e,
``tiny-olmoe.prefill_counted`` 0a7796269c0e,
``tiny-olmoe.prefill_chunk_counted`` 7eba53a3a7e8.
NO digest that was pinned before PR 42 is replaced: the maskless
``prefill`` and ``prefill_chunk`` of both sizes (ISSUE 42 expected
tiny-olmoe's two to move; they are generate's and keep the buckets),
both sizes' decode programs and pool writes, the dense and the
latent-attention families. ``verify_step_paged`` of both sizes is new
in the tool and pinned at the parent's digest: a speculative verify and
a session wake run more than one position a row with no mask and no
capacity for every model of the family, Mixtral's included, and must
stay on the buckets (the review of PR 42 found them on tiles).

What this fence cannot see: the test sizes have 8 experts, and
``moe_tiles.tile_rows`` differs from the parent's rule only where the
experts outnumber an expert's even share of the pairs, which 8 experts
never do. Mellum's real-width programs under 512 tokens a dispatch did
move; tests/test_mellum_parity.py pins the rule over its bucket set
with the parent's values beside them.

PR 43 moved the prefill half of models/pangu._routed_local (``live``
None) from buckets onto the same tiles, for the latent-attention family
and for the hybrid family's LatentMoE; Mellum's routed layers, which
were on the tiles already, now reach them through that function
(nemotron_h._routed_tiles is gone). The tool now also lowers the
latent-attention family's ``_counted`` prefills and its verify step (a
session wake: more than one position a row, ``live`` None, so a prefill
and on tiles), and the ``_counted`` chunk of the two hybrid sizes that
route. ``PR43`` holds the eight digests that differ from the parent's,
taken on PR 43 itself; on the parent (commit 1715d06, the new tool run
on it) they read
``tiny-pangu.prefill`` 985aa303b104,
``tiny-pangu.prefill_chunk`` 81763f0731f3,
``tiny-pangu.prefill_counted`` 2ce771961429,
``tiny-pangu.prefill_chunk_counted`` 9f448938712e,
``tiny-pangu.verify_step_paged`` e0be7e78d05e,
``tiny-nemotron-h.prefill_chunk`` 2a8087dca4b7,
``tiny-nemotron-h.prefill_chunk_counted`` 472f5d375948,
``tiny-mellum2.prefill_chunk_counted`` be88d39debc0.
The last moved by ONE thing: the fourth entry of the counts is now the
rows the tiles multiplied (``sum(tiles) x rows a tile``, which the
parent computed and dropped) where it was a constant 0; the maskless
``tiny-mellum2.prefill_chunk``, whose counts nobody reads, lowers to
the parent's text byte for byte and keeps its pin in ``PARENT`` (ISSUE
43 expected it to move by that sum; it does not, the sum is dead code
there). Every decode digest of the three families holds: the decode
half of ``_routed_local`` is the bucket path as it lowered, written
straight where it was one branch of several."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))

import hash_programs  # noqa: E402

PARENT = {
    "tiny.prefill": "d6edaf81216eb7fb30fedcfc076ff05e63dfa9aa12d0ac9ae97406bcaafaee04",
    "tiny.prefill_chunk": "bf40353f50ae8d3fe22f55fb5b2467ec3f9b234e93a71907d3229928fb3fbf6c",
    "tiny.decode_step_paged": "5bfef2aa51bd0a3e2ad2033d0bedf0d4aa6d81ffb02cb021bff299b867e635d5",
    "tiny.decode_fused": "2b5ebc7755aae77d1e51cbf226fc476dd83063edbaf0a50932358c79681e4f86",
    "tiny.write_prefill_batch": "53d756733b20a7c7bd4959d65a6b3d22fa0ff12af4acb5896d267c8addb291e4",
    "tiny.write_prefill_chunk": "1576d14433a7082cd568b612a6c34cdf383ec71a3c6e7670d40830c538533aca",
    "tiny-moe.prefill": "945eddc721d9bb94e3c9b223dc0f6a67f3fb94f683e34de479c3c48a0cf5956d",
    "tiny-moe.prefill_chunk": "b9bfd5f7272f18d067935b35232b838374296ede31e8ff60eb4a317734d99fbf",
    "tiny-moe.decode_step_paged": "43f3fc3e65e56ee813e130d65061a34017c6173a356f4684511af19a46a1740b",
    "tiny-moe.decode_fused": "d6aa0b48b3de1e6fb1cbc5fc4c30fc8b344442e98c95d420ace38d685118e093",
    "tiny-moe.verify_step_paged": "154a904b240ea3d85b15cffd9952308b2d1ebc7c346a0b0b4d5721a5058a9065",
    "tiny-moe.write_prefill_batch": "53d756733b20a7c7bd4959d65a6b3d22fa0ff12af4acb5896d267c8addb291e4",
    "tiny-moe.write_prefill_chunk": "1576d14433a7082cd568b612a6c34cdf383ec71a3c6e7670d40830c538533aca",
    "tiny-olmoe.prefill": "26a39db12d865d9dfbc24a021f31ab85ffbc70fe0f919874d4e566ba6dde1810",
    "tiny-olmoe.prefill_chunk": "be6d4cf4f8729a8a881ab49653ee007383b5e309e39a5d14f65d777283189461",
    "tiny-olmoe.decode_step_paged": "a1a92274845ae1bfdc4d249b3d29b9dea6cb4a8de4bfce6587b6601ed6121a87",
    "tiny-olmoe.decode_fused": "f0c2ae114434292e5e4ba72a0989c2faf41481401500bc666075d19157579608",
    "tiny-olmoe.verify_step_paged": "6c912595e96663af5ad8a14a2df2f49de813b487b4fc31759ebd54aaefe994fb",
    "tiny-olmoe.write_prefill_batch": "427d1c493a9fcfe813e321dc7eae89372e7149b1d0fa7d780f4e3f575f79207c",
    "tiny-olmoe.write_prefill_chunk": "91a1f2403e57658367328f06ac514914122247be29d0dca99b76306d0f6f165d",
    # The latent-attention family, taken on PR 32's parent (commit
    # bdbf057): PR 32 widened its routed dispatch (a selection bias, the
    # experts' activation, a latent width) for a fourth family and must
    # not have moved what this one lowers to.
    "tiny-pangu.decode_step_paged": "e4a6fbb06dba46721175ad3bc256f89b02b89535b6370f404d7c1e04a126ec74",
    "tiny-pangu.decode_fused": "1cb56c7e7e4a94045b891fcc67daac39af0f40122886d008616f884844c13c7c",
    "tiny-pangu.write_prefill_batch": "9ecc20c4c0cb1821f120c465568e16e13b8a4cf8dbfae3bc0a7a30e362987f50",
    "tiny-pangu.write_prefill_chunk": "cb91ef92e8ef392040a646765c53e1df4fa5aa56e1eb968427049ac279bf3b5a",
    # The hybrid family, taken on PR 42's parent (commit 3b42824): PR 42
    # made nemotron_h._routed_tiles a call of models/moe_tiles.py and
    # must not have moved what Mellum's, Nemotron's or Phi's lower to.
    "tiny-nemotron-h.decode_step_paged": "932546385b870da5fba9df1ac26696a6ec04e841cbd52bf57a893874eedc6487",
    "tiny-phi4flash.prefill_chunk": "8f22bad36d77b59b4b29d01eb0642c0bf38b92a9eb46da2668a4becb7859b4fc",
    "tiny-phi4flash.decode_step_paged": "0d0c35622a61befdd2259ea103ab478fbb6979015c794e9d36babf6733d4b15d",
    "tiny-mellum2.prefill_chunk": "7dfaa925f7d1bb6ecd753365c45255bdcc3ebefac26b2403f4cb8d3deae04366",
    "tiny-mellum2.decode_step_paged": "208add085376db497aeb54b8e684cf51f7b9ad8645ed214ca38a9c1db22cb323"
}

# Taken on PR 42 itself: the programs it meant to change.
PR42 = {
    "tiny-moe.prefill_counted": "639e3b7782d1a33d248ae7de5cabd668ada0b1c67ae414998902abc016e69200",
    "tiny-moe.prefill_chunk_counted": "d5ad8d29d82755bb2fd062a726a3cc3a2e326b5e65713a66d88b5a7f51b520aa",
    "tiny-olmoe.prefill_counted": "66afbe3371e2d0a62780b7b94b9f392be476ed44b14be877c71d0e68515260ef",
    "tiny-olmoe.prefill_chunk_counted": "9ed598610acfe30f91ae98fc058c9d17c17b7fc4fd749db6cb387bfeee56387e"
}
# Taken on PR 43 itself: the programs it meant to change, and the ones
# the tool newly lowers for the families it touched (docstring).
PR43 = {
    "tiny-pangu.prefill": "e809892fb885f1814b6c626e5aa9e3a5d10cb05eed7d353d054aa6fb900f502c",
    "tiny-pangu.prefill_chunk": "cee662ecbecda0f00f1a22da12f7d5f6d739d6cc895f1bebc2535e69d1d1882a",
    "tiny-pangu.prefill_chunk_counted": "eb8af9a8c1ae8570ee9fd19cbc2cc398b9c41d036baf878bd1ad43028f5f9242",
    "tiny-pangu.prefill_counted": "292b11090d2e1deff9b6c020d061e98c60931239d617cbab74b10bffda5c2f69",
    "tiny-pangu.verify_step_paged": "6f275815983c9c98b6b383624f3aa20afbd5dfc5d787dfa9703ba893f32e3e79",
    "tiny-nemotron-h.prefill_chunk": "8a775d1a6c69808dc370820cc95ce93f05fda8c4088b9bb8207155f6354e6e90",
    "tiny-nemotron-h.prefill_chunk_counted": "e6df4473e41757e5848ec6ab476bbbee47449f5b5a40a3f806fc386909ff68fc",
    "tiny-mellum2.prefill_chunk_counted": "94e53d00357a558e0bfb394d3fc190fd1f56ea9ffeb6ed3079a34308a1c677a4"
}
# Taken on PR 45 itself: the test size of the configuration it adds
# (tiny-lfm2: short convolutions, a paired page pool, a dense head
# before one scan over periods of varied length; taken again after the
# benchmark check's refusal, when the tail's scan went into that one). Every digest above held on that tree: the
# router's epsilon, the per-head QK-norm key, the ``-`` layers' own
# width, the bias-less convolution and the walk's head and tail are
# written so that the older test sizes' programs keep their text.
PR45 = {
    "tiny-lfm2.prefill_chunk": "93029a248c9f20cbd3b37b778f8eb206118b08c16ee323a34b94f81880b1ee8f",
    "tiny-lfm2.decode_step_paged": "948b5e32774efabfb48daf9a8a2b0fc2a788766121f2fd93fae7fc065935fd59",
    "tiny-lfm2.prefill_chunk_counted": "8c1013f0a11c4d1e89a3cafeaba03ad11fbe8733d9996779362b35dd25769d8c"
}
# Taken on PR 49 itself: the test size of the configuration it adds
# (tiny-keye: attention whose queries read the 16 keys an indexer picks,
# an index key a token in the caches, ``sE`` four times as one scan).
# Every digest above held on that tree: the index key is a leaf only an
# indexed model's caches have, the write ops take it as an argument that
# is None elsewhere, the walk's carries grow behind what they held, and
# the one-head decode write a latent pool and the index keys now share
# keeps the latent pool's text.
PR49 = {
    "tiny-keye.prefill_chunk": "e03eabe9c2551c35d9ff6634c67f4d0bd53d445da580aa7a9160259cf5bdbcea",
    "tiny-keye.decode_step_paged": "1e35cb01f87bb551aa2762e043c8055de1b7d78d0619b1b3c24ec66b768c61c9",
    "tiny-keye.prefill_chunk_counted": "dbbd82669f6c94fca49ad9d2cb505ec12e4edb1f6b5a8ec35aee601ad5021f25"
}
# Taken on PR 50 itself (tools/hash_programs.py under this suite's
# conftest): the one digest that PR moved. PR 49 had wrapped an indexed
# model's ``_counted`` chunk in a ``lax.cond`` on whether any position
# of it was real; PR 50 put that test into the scheduler's chunk program
# for every family (serve/scheduler.py ``_unless_padding``) and took it
# out of the model, whose chunk is the plain forward again (on PR 50's
# parent it read dbbd82669f6c). The other 45 digests held on that tree:
# no other model-level program changed its text.
PR50 = {
    "tiny-keye.prefill_chunk_counted": "0f536a992de348d8d043c152111cf2f6314dfb0e23418a5096045e5515ab2517"
}
PINNED = {**PARENT, **PR42, **PR43, **PR45, **PR49, **PR50}


@pytest.fixture(scope="module")
def texts():
    return {name: hash_programs.programs(name)
            for name in hash_programs.CONFIGS}


@pytest.mark.parametrize("key", sorted(PINNED))
def test_program_lowers_to_the_parents_stablehlo(texts, key):
    import hashlib
    name, label = key.split(".", 1)
    got = hashlib.sha256(texts[name][label].encode()).hexdigest()
    assert got == PINNED[key], (
        f"{key} lowers to other StableHLO than at its pinned commit")


def test_every_program_the_tool_lowers_is_pinned(texts):
    assert {f"{name}.{label}" for name, labels in texts.items()
            for label in labels} == set(PINNED)

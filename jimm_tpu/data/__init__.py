from jimm_tpu.data.pipeline import PrefetchIterator
from jimm_tpu.data.preprocess import (CLIP_MEAN, CLIP_STD, IMAGENET_MEAN,
                                      IMAGENET_STD, SIGLIP_MEAN, SIGLIP_STD,
                                      center_crop, native_available,
                                      preprocess_batch, resize_bilinear,
                                      to_float_normalized)
from jimm_tpu.data.clip_tokenizer import CLIPTokenizer
from jimm_tpu.data.naflex import (image_to_patches, patchify_naflex,
                                  target_size_for_max_patches)
from jimm_tpu.data.grain_pipeline import (TFRecordDataSource,
                                          grain_batches, make_grain_loader)
from jimm_tpu.data.records import (classification_batches, decode_image,
                                   image_text_batches, iter_examples,
                                   naflex_image_text_batches,
                                   pad_tokens, prep_image, resolve_paths,
                                   write_classification_records,
                                   write_image_text_records)
from jimm_tpu.data.synthetic import (blob_classification, contrastive_pairs,
                                     naflex_contrastive_pairs,
                                     token_sequences)
from jimm_tpu.data.webdataset import (iter_wds_examples, resolve_tar_paths,
                                      wds_classification_batches,
                                      wds_image_text_batches, write_wds_shard)
from jimm_tpu.data.tfrecord import (TFRecordWriter, crc32c, decode_example,
                                    encode_example, masked_crc32c,
                                    read_tfrecord, write_tfrecord)

__all__ = [
    "PrefetchIterator", "blob_classification", "contrastive_pairs",
    "naflex_contrastive_pairs", "token_sequences",
    "patchify_naflex", "image_to_patches", "target_size_for_max_patches",
    "preprocess_batch", "to_float_normalized", "resize_bilinear",
    "center_crop", "native_available", "IMAGENET_MEAN", "IMAGENET_STD",
    "CLIP_MEAN", "CLIP_STD", "SIGLIP_MEAN", "SIGLIP_STD",
    "TFRecordWriter", "write_tfrecord", "read_tfrecord", "crc32c",
    "masked_crc32c", "encode_example", "decode_example",
    "image_text_batches", "naflex_image_text_batches",
    "classification_batches", "iter_examples",
    "decode_image", "resolve_paths", "prep_image", "pad_tokens",
    "write_image_text_records", "write_classification_records",
    "TFRecordDataSource", "make_grain_loader", "grain_batches",
    "CLIPTokenizer",
    "wds_image_text_batches", "wds_classification_batches",
    "iter_wds_examples", "resolve_tar_paths", "write_wds_shard",
]

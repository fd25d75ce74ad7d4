// Parquet footer prune/filter engine (component C3' — TPU-build equivalent
// of reference src/main/cpp/src/NativeParquetJni.cpp, pure CPU).
//
// Behavior parity targets:
//   * schema-tree column pruning from a depth-first (names, num_children)
//     request, case-sensitive or case-insensitive
//     (reference NativeParquetJni.cpp:100-368);
//   * row-group filtering to a partition byte range by the parquet-mr
//     midpoint rule, with the PARQUET-2078 bad-file_offset fallback
//     (reference NativeParquetJni.cpp:370-450);
//   * column_orders and per-row-group chunk gathering
//     (reference NativeParquetJni.cpp:483-492,525-540);
//   * re-serialization with PAR1 magic + footer-length framing
//     (reference NativeParquetJni.cpp:589-623).
//
// Implementation is original: footers are held as a generic thrift value
// tree (thrift_compact.hpp) and edited in place by parquet.thrift field id,
// so unknown/future fields pass through untouched.

#pragma once

#include <cstdint>
#include <string>
#include <vector>

#include "tpudf/thrift_compact.hpp"

namespace tpudf {
namespace parquet {

// parquet.thrift field ids used by the engine (public format spec).
namespace fid {
// FileMetaData
constexpr int16_t kSchema = 2;
constexpr int16_t kNumRows = 3;
constexpr int16_t kRowGroups = 4;
constexpr int16_t kColumnOrders = 7;
// SchemaElement
constexpr int16_t kSeType = 1;
constexpr int16_t kSeTypeLength = 2;
constexpr int16_t kSeRepetition = 3;
constexpr int16_t kSeName = 4;
constexpr int16_t kSeNumChildren = 5;
constexpr int16_t kSeConvertedType = 6;
constexpr int16_t kSeScale = 7;
// RowGroup
constexpr int16_t kRgColumns = 1;
constexpr int16_t kRgNumRows = 3;
constexpr int16_t kRgFileOffset = 5;
constexpr int16_t kRgTotalCompressedSize = 6;
// ColumnChunk
constexpr int16_t kCcMetaData = 3;
// ColumnMetaData
constexpr int16_t kCmTotalCompressedSize = 7;
constexpr int16_t kCmDataPageOffset = 9;
constexpr int16_t kCmDictionaryPageOffset = 11;
}  // namespace fid

// UTF-8-aware lower-casing (ASCII + Latin-1 supplement; other code points
// pass through). The reference's mbstowcs/towlower version is
// locale-dependent and self-described as "probably good enough"
// (NativeParquetJni.cpp:40-77); this one is deterministic.
std::string utf8_to_lower(std::string const& in);

// One leaf of a (pruned) schema, as a reader maps it to a column type.
struct LeafInfo {
  int32_t physical = 0;
  int32_t converted = -1;   // parquet ConvertedType, -1 = absent
  int32_t scale = 0;
  int32_t type_length = 0;
  int32_t repetition = 0;   // 0 required, 1 optional, 2 repeated
};

// A parsed footer plus the operations the JNI surface exposes.
class Footer {
 public:
  // Parse from raw thrift bytes (no PAR1 framing). Throws on malformed
  // input; same anti-bomb limits as the reference.
  static Footer parse(uint8_t const* buf, uint64_t len);

  // Prune the schema to the requested column tree: `names` and
  // `num_children` flattened depth-first, root excluded;
  // `parent_num_children` = number of root children requested. Prunes the
  // schema list and column_orders and remembers the chunk gather map for
  // filter_columns(). Does NOT touch row groups: the midpoint filter must
  // see the file's original first column, so call order is
  // prune_columns -> filter_row_groups -> filter_columns (the reference
  // orders readAndFilter the same way, NativeParquetJni.cpp:524-545).
  void prune_columns(std::vector<std::string> const& names,
                     std::vector<int32_t> const& num_children,
                     int32_t parent_num_children, bool ignore_case);

  // Keep only row groups whose midpoint falls in
  // [part_offset, part_offset + part_length). Negative part_length = keep
  // all (reference NativeParquetJni.cpp:542-544 gates on part_length >= 0).
  void filter_row_groups(int64_t part_offset, int64_t part_length);

  // Gather each surviving row group's column chunks to the pruned columns
  // (reference filter_columns, NativeParquetJni.cpp:483-492). Requires a
  // prior prune_columns call.
  void filter_columns();

  // What a reader needs to decode exactly what this footer kept: the
  // file's indices of the surviving row groups and of the pruned leaves (in
  // request order; a requested name the file lacks leaves no entry). Both
  // are the file's own numbering, which parquet::read_file takes.
  std::vector<int32_t> const& kept_row_groups() const { return kept_groups_; }
  std::vector<int> const& kept_leaves() const { return chunk_gather_; }
  // For each kept leaf, its position among the REQUEST's leaves: a
  // position that is absent names a requested column the file lacks.
  std::vector<int> const& kept_requests() const { return chunk_request_; }
  int32_t file_leaves() const { return file_leaves_; }  // before any prune

  // The schema's leaves in order (after prune_columns: request order).
  std::vector<LeafInfo> leaves() const;
  // Row counts of the remaining row groups, in order.
  std::vector<int64_t> row_group_rows() const;
  // Sum of total_compressed_size over every remaining column chunk (after
  // filter_columns: the pruned leaves' chunks of the kept row groups).
  int64_t compressed_bytes() const;

  int64_t num_rows() const;     // sum of remaining row-group num_rows
  int32_t num_columns() const;  // root schema element's num_children

  // Compact-serialize with PAR1 + length framing:
  // [PAR1][thrift bytes][u32 LE length][PAR1].
  std::string serialize_framed() const;

  thrift::Value& root() { return meta_; }
  thrift::Value const& root() const { return meta_; }

 private:
  explicit Footer(thrift::Value meta) : meta_(std::move(meta)) {}
  thrift::Value meta_;
  std::vector<int> chunk_gather_;
  std::vector<int> chunk_request_;
  std::vector<int32_t> kept_groups_;  // file indices of meta_'s row groups
  int32_t file_leaves_ = 0;
  bool pruned_ = false;
};

}  // namespace parquet
}  // namespace tpudf
